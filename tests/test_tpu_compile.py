"""The chip's compiler, without the chip.

libtpu is installed here and compiles for a TPU that is *described*, not
attached (``on-chip-measurement`` guide §2, third rehearsal). These tests
hand it the Pallas kernels at the shapes ``chip_smoke.py`` runs — what the
interpret-mode tests can never see: a refused op (the fused Adam's scalar
``pow`` was one), an unaligned block, too much VMEM.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under xdist every
worker imports every test file. All such tests live in this one file.
A compile that passes here is not a chip run.
"""

import importlib
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chip_smoke import Plan  # noqa: E402

fa = importlib.import_module("kubeshare_tpu.ops.flash_attention")
fad = importlib.import_module("kubeshare_tpu.ops.fused_adam")

PLAN = Plan()


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Shapes placed on one described chip — with the persistent compile
    cache off: an entry written by such a compile cannot be read back
    without a chip, and the next run would warn on every test."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    sharding = SingleDeviceSharding(topo.devices[0])

    def shaped(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    yield shaped
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_count(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


#: The smoke's shapes (float32) and the attention calls of the benchmark's
#: cells (bfloat16: the small trainer's, the medium trainer's, the scorer's
#: shortest bucket) — all at the tiles the kernel derives from the shape,
#: so Mosaic's verdict on the tile and its VMEM is a test.
FLASH_CASES = ([(*case, jnp.float32) for case in PLAN.kernel_cases]
               + [(8, 1024, 12, 12, 64, jnp.bfloat16),
                  (2, 1024, 16, 16, 64, jnp.bfloat16),
                  (1, 128, 16, 16, 64, jnp.bfloat16)])


@pytest.mark.parametrize("window", [None, PLAN.kernel_window])
@pytest.mark.parametrize("b,s,h,hk,d,dtype", FLASH_CASES)
def test_flash_forward_compiles_for_v5e(one_chip, b, s, h, hk, d, dtype,
                                        window):
    q, kv = one_chip((b, s, h, d), dtype), one_chip((b, s, hk, d), dtype)
    compiled = jax.jit(
        lambda q, k, v: fa._flash_fwd(q, k, v, True, None, None, None,
                                      window)
    ).lower(q, kv, kv).compile()
    assert _kernel_count(compiled) == 1


@pytest.mark.parametrize("window", [None, PLAN.kernel_window])
@pytest.mark.parametrize("b,s,h,hk,d,dtype", FLASH_CASES)
def test_flash_dq_and_dkv_compile_for_v5e(one_chip, b, s, h, hk, d, dtype,
                                          window):
    q, kv = one_chip((b, s, h, d), dtype), one_chip((b, s, hk, d), dtype)
    o, lse = one_chip((b, s, h, d)), one_chip((b * h, s, 1))
    compiled = jax.jit(
        lambda q, k, v, o, lse, g: fa._flash_bwd(
            q, k, v, o, lse, g, None, True, None, None, None, window)
    ).lower(q, kv, kv, o, lse, o).compile()
    assert _kernel_count(compiled) == 2     # the dQ pass and the dK/dV pass


@pytest.mark.parametrize("n", PLAN.adam_sizes)
def test_fused_adam_compiles_for_v5e(one_chip, n):
    x = one_chip((n,))
    compiled = jax.jit(
        lambda p, g, m, v: fad.adam_update(p, g, m, v, step=3)
    ).lower(x, x, x, x).compile()
    assert _kernel_count(compiled) == 1


def _kernel_names(compiled) -> list[str]:
    """The HLO instruction names of the program's Pallas kernels: what
    their events on the trace's ``XLA Ops`` line are called."""
    import re
    return sorted(m.group(1).rstrip(".0123456789") for m in re.finditer(
        r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        compiled.as_text()))


def test_the_four_kernels_are_told_apart_by_name_in_the_tpu_program(one_chip):
    b, s, h, hk, d = PLAN.kernel_cases[0]
    q, kv = one_chip((b, s, h, d)), one_chip((b, s, hk, d))

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    assert _kernel_names(compiled) == ["flash_dkv", "flash_dq", "flash_fwd"]
    x = one_chip((PLAN.adam_sizes[0],))
    compiled = jax.jit(
        lambda p, g, m, v: fad.adam_update(p, g, m, v, step=3)
    ).lower(x, x, x, x).compile()
    assert _kernel_names(compiled) == ["fused_adam"]


# -- what a proxy-attached pod ships ---------------------------------------
# No topology needed: the pod traces on its CPU backend and exports for the
# proxy's platform (isolation/client.py _trace_and_compile). The program
# that reaches the TPU must carry the Mosaic kernel, not the interpreter.

def _exported_for(platform, fn, *specs) -> str:
    from jax import export
    return export.export(jax.jit(fn), platforms=[platform])(
        *specs).mlir_module()


def test_cpu_traced_export_for_tpu_carries_the_compiled_flash_kernel():
    q = jax.ShapeDtypeStruct((2, 256, 8, 32), jnp.float32)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).sum()

    assert jax.devices()[0].platform == "cpu"     # the tracing process
    text = _exported_for("tpu", jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert text.count("tpu_custom_call") == 3     # fwd, dQ, dK/dV
    assert "tpu_custom_call" not in _exported_for(
        "cpu", jax.grad(loss, argnums=(0, 1, 2)), q, q, q)


def test_cpu_traced_export_for_tpu_carries_the_compiled_adam_kernel():
    x = jax.ShapeDtypeStruct((4096,), jnp.float32)

    def step(p, g, m, v):
        return fad.adam_update(p, g, m, v, step=1)

    assert _exported_for("tpu", step, x, x, x, x).count(
        "tpu_custom_call") == 1
    assert "tpu_custom_call" not in _exported_for("cpu", step, x, x, x, x)
