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
import numpy as np
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
                  (1, 128, 16, 16, 64, jnp.bfloat16),
                  # the LFM2 trainer's: 4 query heads a kv head, 4 x 4 tiles
                  (2, 4096, 32, 8, 64, jnp.bfloat16)])


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


#: The cells' attention calls that must ride on lane blocks of the model's
#: own array: the two GPT-2 trainers', the scorer's shortest bucket, and the
#: LFM2 trainer's 32-on-8 call (which took them by measurement, PERF.md).
LANE_CALLS = [(8, 1024, 12, 12, 64), (2, 1024, 16, 16, 64),
              (1, 128, 16, 16, 64), (2, 4096, 32, 8, 64)]


@pytest.mark.parametrize("b,s,h,hk,d", LANE_CALLS)
def test_flash_moves_no_operand_around_its_three_kernels(one_chip, b, s, h,
                                                         hk, d):
    """Forward and backward through the public entry, bfloat16 as the cells
    run it: the compiled program holds the three Pallas calls the plan
    names and NOTHING that transposes or copies an array of the operands'
    size (what ``_fold`` / ``_unfold``, the cast of dO and D's relayout
    were), and the VMEM the plan asked for is what the rule counted, which
    Mosaic granted (or the compile above would have refused)."""
    import re

    # as a model hands them over: the heads split off a (batch, seq,
    # heads·head_dim) activation by a reshape, and merged back by one (a
    # rank-4 ENTRY parameter would get a layout of XLA's own choosing)
    q = one_chip((b, s, h * d), jnp.bfloat16)
    kv = one_chip((b, s, hk * d), jnp.bfloat16)
    w = one_chip((b, s, h * d))

    def loss(q, k, v, w):
        o = fa.flash_attention(q.reshape(b, s, h, d), k.reshape(b, s, hk, d),
                               v.reshape(b, s, hk, d), causal=True)
        return (o.reshape(b, s, h * d) * w).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv, w).compile()
    assert _kernel_names(compiled) == ["flash_dkv", "flash_dq", "flash_fwd"]
    text = compiled.as_text()
    moved = [(kind, dims) for dims, kind in re.findall(
        r" = \w+\[([\d,]+)\]\S* (copy|transpose)\(",
        text[text.index("ENTRY"):])
        if np.prod([int(n) for n in dims.split(",")]) >= b * s * hk * d]
    assert moved == []

    plan = fa._blocks(s, s, d, jnp.bfloat16, None, None, True, None, h, hk)
    assert (plan.addressing, plan.heads, plan.chunk) == (
        "lanes", 2, min(s, fa.CHUNK_TARGET))
    need = fa._tile_vmem_bytes(
        plan.block_q, plan.block_k,
        plan.chunk if s == plan.block_q else plan.block_q, 128, plan.heads, 2)
    assert need <= fa.VMEM_BUDGET
    assert plan.vmem_limit == (None if need <= 16 * 2 ** 20 else need)


@pytest.mark.parametrize("shape", PLAN.adam_sizes)
def test_fused_adam_compiles_for_v5e(one_chip, shape):
    x = one_chip(tuple(np.atleast_1d(shape)))
    compiled = jax.jit(
        lambda p, g, m, v: fad.adam_update(p, g, m, v, step=3)
    ).lower(x, x, x, x).compile()
    assert _kernel_count(compiled) == 1


#: One leaf of every shape the benchmark's two trainers hold (GPT-2 small
#: and medium as `models/transformer.py` builds them), at full size: the
#: vocabulary in rows, in lanes (kept transposed on the chip) and 1-D.
CELL_LEAVES = [(768, 768), (768, 2304), (768, 3072), (3072, 768),
               (1024, 768), (50257, 768), (768, 50257), (768,), (3072,),
               (50257,), (1024, 1024), (1024, 3072), (1024, 4096),
               (4096, 1024), (50257, 1024), (1024, 50257), (1024,), (4096,)]


#: One leaf of every shape the LFM2 trainer holds (LFM2-24B-A2B's share as
#: `models/lfm2.py` builds it): the experts' stacks of rank 3 at 100 MB
#: each, the fused q/k/v, the dense MLP, the taps, the router and its bias.
LFM2_LEAVES = [(8, 2048, 1536), (8, 1536, 2048), (2048, 6144), (2048, 2048),
               (2048, 3072), (2048, 11776), (11776, 2048), (3, 2048),
               (2048, 64), (64,), (2048,), (8192, 2048)]


@pytest.mark.parametrize("leaves", [CELL_LEAVES, LFM2_LEAVES],
                         ids=["gpt2", "lfm2"])
def test_fused_adam_moves_each_leaf_once_in_a_step_that_donates_nothing(
        one_chip, leaves):
    """Through the optax wrapper, as every tenant runs it: one kernel a
    leaf, and around it nothing that copies a leaf. A leaf the kernel's
    view of which is not how the chip stores it shows as a ``copy`` or a
    ``transpose`` here; an aliased output as a ``copy`` in front of the
    call. (XLA's own ``copy-start`` of an operand into VMEM ahead of the
    call is a prefetch, not a second pass over HBM.)"""
    import re

    import optax

    params = {f"leaf{i:02d}": one_chip(s) for i, s in enumerate(leaves)}
    optimizer = fad.fused_adam(1e-3)
    state = jax.tree_util.tree_map(
        lambda s: one_chip(s.shape, s.dtype),
        jax.eval_shape(optimizer.init, params))

    def step(params, state, grads):
        updates, state = optimizer.update(grads, state, params)
        return optax.apply_updates(params, updates), state

    compiled = jax.jit(step).lower(params, state, params).compile()
    assert _kernel_names(compiled) == ["fused_adam"] * len(leaves)
    text = compiled.as_text()
    moved = [(kind, dims) for dims, kind in re.findall(
        r" = \w+\[([\d,]+)\]\S* (copy|pad|slice|transpose|reshape)\(",
        text[text.index("ENTRY"):])
        if np.prod([int(d) for d in dims.split(",")]) >= 768]
    # an asynchronous copy whose target is not VMEM (``S(1)``) is a copy
    # of the leaf in HBM all the same: how XLA copies a large aliased leaf
    moved += [("copy-start", layout) for layout in re.findall(
        r" = \(\w+\[[\d,]+\](\S*), [^=]* copy-start\(", text)
        if "S(1)" not in layout]
    assert moved == []


@pytest.mark.parametrize("shape", CELL_LEAVES + LFM2_LEAVES)
def test_fused_adam_block_is_large_and_inside_its_vmem_limit(shape):
    """The block the rule gives each of the cells' leaves: what the
    pipeline holds of it (seven operands, double-buffered, padded to
    tiles) is inside the limit the call declares, the limit inside what
    the flash kernels allow themselves, and no leaf takes more grid
    steps than its bytes over half the block's budget."""
    view = jax.eval_shape(fad._as_stored,
                          jax.ShapeDtypeStruct(shape, jnp.float32)).shape
    block, limit = fad._block(view, jnp.float32)
    padded = (-(-block[0] // 1024) * 1024 if len(block) == 1
              else -(-block[0] // 8) * 8 * -(-block[1] // 128) * 128)
    assert 14 * padded * 4 <= limit <= fa.VMEM_BUDGET
    steps = int(np.prod([-(-n // b) for n, b in zip(view, block)]))
    assert steps <= 1 + 4 * int(np.prod(shape)) // (fad.BLOCK_BYTES // 2)


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


def test_grouped_expert_products_compile_for_v5e_at_published_widths(
        one_chip):
    """The LFM2 expert layer's share at its published widths (8 of 64
    experts of 2048 x 1536, top 4, 8,192 tokens: a sorted buffer of
    32,768 rows), forward and backward: three grouped products forward,
    three back for the rows' gradients, three transposed ones for the
    matrices', every one a Mosaic kernel at the tiles the shapes give; the
    rows move between token order and the sorted buffer by four
    ``moe_rows`` kernels (``dispatch`` and ``combine``, each way), never by
    a scatter of rows."""
    import re

    moe = importlib.import_module("kubeshare_tpu.ops.moe")
    params = jax.tree_util.tree_map(
        lambda s: one_chip(s.shape, s.dtype),
        jax.eval_shape(lambda k: moe.topk_moe_init(k, 2048, 1536, 64, 8),
                       jax.random.PRNGKey(0)))
    x = one_chip((2, 4096, 2048), jnp.bfloat16)

    def loss(p, x):
        y = moe.topk_moe_apply(p, x, 4, 0, dtype=jnp.bfloat16)
        return (y.astype(jnp.float32) ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile()
    assert _kernel_names(compiled) == (["gmm"] * 6 + ["moe_rows"] * 4
                                       + ["tgmm"] * 3)
    text = compiled.as_text()
    scattered = [int(np.prod([int(d) for d in dims.split(",")]))
                 for dims in re.findall(r" = \w+\[([\d,]+)\]\S* scatter\(",
                                        text)]
    assert all(n < 1024 for n in scattered), scattered


@pytest.mark.parametrize("tokens, held, train, rows_kernels", [
    (16384, 8, True, 3),     # combine's way back: 128 MiB of float32 rows
    (32768, 8, True, 2),     # and 128 MiB of the layer's input
    (32768, 8, False, 1),    # one 32k-token document scored
    (8192, 64, True, 4),     # every expert held: 64 runs a token block
    (8184, 8, True, 4),      # no tile of 16 divides it: padded to 8,192
])
def test_expert_layer_compiles_for_v5e_past_the_cells_size(
        one_chip, tokens, held, train, rows_kernels):
    """The expert layer at its published widths where the tokens' rows
    outgrow the VMEM a call of the buffer side keeps them in (that move
    then goes by XLA's row gathers: fewer ``moe_rows`` kernels), where
    every expert is held (the token side stages 64 runs a block), or where
    the token count is padded: each compiles for a v5e, with no scatter of
    rows."""
    import re

    moe = importlib.import_module("kubeshare_tpu.ops.moe")
    params = jax.tree_util.tree_map(
        lambda s: one_chip(s.shape, s.dtype),
        jax.eval_shape(lambda k: moe.topk_moe_init(k, 2048, 1536, 64, held),
                       jax.random.PRNGKey(0)))
    x = one_chip((1, tokens, 2048), jnp.bfloat16)

    def loss(p, x):
        y = moe.topk_moe_apply(p, x, 4, 0, dtype=jnp.bfloat16)
        return (y.astype(jnp.float32) ** 2).sum()

    f = jax.grad(loss, argnums=(0, 1)) if train else (
        lambda p, x: moe.topk_moe_apply(p, x, 4, 0, dtype=jnp.bfloat16))
    compiled = jax.jit(f).lower(params, x).compile()
    names = _kernel_names(compiled)
    assert names.count("moe_rows") == rows_kernels, names
    assert names.count("gmm") == (6 if train else 3), names
    scattered = [int(np.prod([int(d) for d in dims.split(",")]))
                 for dims in re.findall(r" = \w+\[([\d,]+)\]\S* scatter\(",
                                        compiled.as_text())]
    assert all(n < 1024 for n in scattered), scattered


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


# -- the long-context scorer's kernels (MiniCPM-SALA's widths, PR 34) ----------

la = importlib.import_module("kubeshare_tpu.ops.linear_attention")
spa = importlib.import_module("kubeshare_tpu.ops.sparse_attention")


@pytest.mark.parametrize("s", [128, 1024, 32768])
def test_lightning_scan_compiles_for_v5e(one_chip, s):
    """32 heads of 128 lanes, bfloat16, at a chat bucket and at the longest
    document: a head is a lane block of the model's own array (nothing is
    copied around the one kernel), the rates ride in scalar memory."""
    x = one_chip((1, s, 32, 128), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v, lam: la.lightning_attention(q, k, v, lam)
    ).lower(x, x, x, one_chip((32,))).compile()
    assert _kernel_names(compiled) == ["lightning_scan"]
    assert " transpose(" not in compiled.as_text()


@pytest.mark.parametrize("s", [16384, 32768])
def test_attention_over_chosen_blocks_compiles_for_v5e(one_chip, s):
    """The two buckets past ``dense_len``: 16 query heads a kv head share a
    step's mask; the VMEM the call asks for is granted."""
    q = one_chip((1, s, 32, 128), jnp.bfloat16)
    kv = one_chip((1, s, 2, 128), jnp.bfloat16)
    bits = one_chip((1, 2, s, s // 64 // 32), jnp.int32)
    compiled = jax.jit(
        lambda q, k, v, b: spa.chosen_blocks_attention(q, k, v, b, 64)
    ).lower(q, kv, kv, bits).compile()
    assert _kernel_names(compiled) == ["chosen_blocks_attention"]


def test_block_selection_compiles_for_v5e_in_blocks_of_queries(one_chip):
    """The selection at 32,768 tokens never holds the per-head scores
    whole (32,768 x 32 x 2,047 float32 = 8.6 GB): a block of queries at a
    time, under a gigabyte of temporaries."""
    q = one_chip((1, 32768, 32, 128), jnp.bfloat16)
    k = one_chip((1, 32768, 2, 128), jnp.bfloat16)
    compiled = jax.jit(lambda q, k: spa.select_blocks(
        q, k, kernel_size=32, stride=16, block_size=64, init_blocks=1,
        window_blocks=32, topk=64)).lower(q, k).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


def test_flash_forward_takes_32_on_2_heads_of_128_for_v5e(one_chip):
    """The sparse layers' dense path at its longest (8,192 tokens, 16 query
    heads a kv head, 128 lanes a head): on lane blocks, one kernel."""
    q = one_chip((1, 8192, 32, 128), jnp.bfloat16)
    kv = one_chip((1, 8192, 2, 128), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True)
    ).lower(q, kv, kv).compile()
    assert _kernel_names(compiled) == ["flash_fwd"]
    plan = fa._blocks(8192, 8192, 128, jnp.bfloat16, None, None, True, None,
                      32, 2)
    assert (plan.addressing, plan.heads) == ("lanes", 1)
