"""chip_smoke.py rehearsed on CPU, plus the pieces it leans on.

The rehearsal drives the smoke's own phase functions with a small
:class:`chip_smoke.Plan` — the test-only hook: no option of the program
selects it. What a rehearsal cannot show (compiled kernels, a real chip's
exclusivity, times) is the chip run's to show; what it can: paths,
arguments, control flow, the parent staying off JAX, loud failure on a
device that is not a TPU, and the last-line format.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

CPU_ENV = {"JAX_PLATFORMS": "cpu",
           "KUBESHARE_TPU_TRANSFORMER_PRESET": "small",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}


def rehearsal_plan(**over) -> chip_smoke.Plan:
    plan = dict(platform="cpu", child_env=dict(CPU_ENV),
                transformer_steps=400, gate_model="mnist", gate_steps=3,
                gang_steps=2,
                kernel_cases=((1, 16, 4, 2, 8),), kernel_window=8,
                adam_sizes=(1000,), split_tol=0.5, gate_mem_grant=False,
                port_shift=3000, fresh_native_build=False,
                child_timeout_s=300.0)
    plan.update(over)
    return chip_smoke.Plan(**plan)


def _run_parent(code: str, timeout: float = 600.0):
    """Run smoke code in a FRESH parent (this pytest process has long
    imported jax, which is exactly what the smoke's parent must not)."""
    env = dict(os.environ, PYTHONPATH=str(REPO))
    return subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=str(REPO), capture_output=True, text=True,
                          timeout=timeout)


_REHEARSE = """
import json, sys
sys.path.insert(0, {tests!r})
from test_chip_smoke import rehearsal_plan
import chip_smoke
plan = rehearsal_plan(**{over!r})
device = chip_smoke.run(plan, chips={chips})
assert "jax" not in sys.modules, "the smoke's parent imported jax"
print(json.dumps({{"ok": True, "device": device}}))
"""


def test_one_chip_phases_rehearse_on_cpu():
    proc = _run_parent(_REHEARSE.format(tests=str(REPO / "tests"), over={},
                                        chips=1))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    out = proc.stdout
    for marker in ("native cores built", "token core NativeTokenCore",
                   "daemon discovery while the proxy owns the chip",
                   "device-time split", "exclusive:", "gate pod:",
                   "eager pod:", "fused adam n=1000", "compile cache"):
        assert marker in out, (marker, out[-3000:])
    last = json.loads(out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 1}}


@pytest.mark.slow
def test_four_chip_phase_rehearses_on_virtual_devices():
    four = "--xla_force_host_platform_device_count=4"
    over = {"child_env": dict(CPU_ENV, XLA_FLAGS=four),
            "gang_member_env": {"XLA_FLAGS": CPU_ENV["XLA_FLAGS"]},
            "gang_single_env": {"XLA_FLAGS": four}}
    proc = _run_parent(_REHEARSE.format(tests=str(REPO / "tests"), over=over,
                                        chips=4))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    out = proc.stdout
    assert "four processes x one chip" in out
    assert "one process x four chips" in out
    assert "proxy owns" not in out and "gate pod" not in out   # no other phase
    assert json.loads(out.strip().splitlines()[-1])["device"]["count"] == 4


def test_smoke_refuses_a_device_that_is_not_a_tpu():
    """As the driver runs it first: in a sandbox with no accelerator the
    program must exit non-zero and print no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          env=env, cwd=str(REPO), capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0
    assert "FAILED in phase control-plane" in proc.stdout
    assert "needs 'tpu'" in proc.stdout
    assert '"ok"' not in proc.stdout


@pytest.fixture
def workdir(tmp_path):
    yield tmp_path
    chip_smoke._kill_all()


def _control_plane(workdir):
    """Phase 1 under the rehearsal plan: the later phases' input."""
    return chip_smoke.phase_control_plane(rehearsal_plan(), workdir)


@pytest.mark.parametrize("phase", ["proxy-attach", "gate-attach", "kernels"])
def test_each_chip_phase_fails_loudly_off_tpu(workdir, phase):
    """Not rehearsing (the default plan wants a TPU) but held to the CPU:
    every phase that owns the chip names itself and the platform."""
    cp = _control_plane(workdir)
    plan = rehearsal_plan(platform="tpu", transformer_steps=2,
                          kernel_cases=(), adam_sizes=())
    run = {"proxy-attach": lambda: chip_smoke.phase_proxy_attach(
               plan, workdir, cp),
           "gate-attach": lambda: chip_smoke.phase_gate_attach(
               plan, workdir, cp),
           "kernels": lambda: chip_smoke.phase_kernels(plan, workdir)}[phase]
    with pytest.raises(chip_smoke.PhaseFailed) as err:
        run()
    assert err.value.phase == phase
    assert "platform 'cpu'" in str(err.value)


def test_main_prints_the_contract_line_last_and_only_on_success(
        monkeypatch, capsys):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "run", lambda plan, chips: device)
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": 1}}')

    def boom(plan, chips):
        raise chip_smoke.PhaseFailed("kernels", "non-finite")
    monkeypatch.setattr(chip_smoke, "run", boom)
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr().out
    assert "FAILED in phase kernels" in out and '"ok"' not in out


# -- the compile-cache helper ------------------------------------------------

_CACHE_PROBE = """
import json, jax
before = jax.config.jax_compilation_cache_dir
from kubeshare_tpu.utils import compilecache
path = compilecache.enable_compile_cache()
assert compilecache.enable_compile_cache() == path      # idempotent
print(json.dumps([before, jax.config.jax_compilation_cache_dir, path]))
"""


def _cache_probe(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    before, after, path = json.loads(proc.stdout.strip().splitlines()[-1])
    return before, after, path, proc.stderr


def test_cache_helper_sets_nothing_where_the_env_names_a_dir(tmp_path):
    before, after, path, err = _cache_probe(str(tmp_path))
    assert before == after == path == str(tmp_path)   # jax read the env itself
    assert f"compile cache {tmp_path}: requests=" in err


def test_cache_helper_uses_the_checkout_when_the_env_is_unset():
    before, after, path, _ = _cache_probe("")
    assert before is None
    assert after == path == str(REPO / ".jax_cache")   # fixed: no pid, no time


# -- daemon discovery never takes the chip -------------------------------------

_DISCOVER = """
import sys
from kubeshare_tpu.topology.discovery import discover_chips
chips = discover_chips("jax", host="n0", state_dir={state!r})
assert "jax" not in sys.modules, "discovery initialized JAX in the daemon"
print(len(chips), chips[0].chip_id)
"""


def test_daemon_discovery_runs_in_a_child_and_leaves_an_inventory(tmp_path):
    state = str(tmp_path / "state")
    proc = _run_parent(_DISCOVER.format(state=state), timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    n, first = proc.stdout.split()
    assert int(n) >= 1 and first.endswith("-n0-0")
    inv = json.loads((tmp_path / "state" / "chips.json").read_text())
    assert inv["platform"] == "cpu" and inv["host"] == "n0"


def test_daemons_start_from_the_inventory_while_the_chip_is_held(
        tmp_path, monkeypatch):
    """A proxy owns the chip: a fresh probe would be refused, so launcherd,
    configd and the collector must come up from what the first probe left
    — and must fail, not invent chips, where there is no inventory."""
    from kubeshare_tpu.topology import discovery

    discovery.node_inventory("n0", str(tmp_path))           # the first probe
    probes = []

    def held(*a, **k):
        probes.append(a)
        raise RuntimeError("chip discovery child failed: TPU already in use")
    monkeypatch.setattr(discovery, "probe_node", held)

    chips = discovery.discover_chips("jax", host="n0",
                                     state_dir=str(tmp_path))
    assert chips and not probes
    from kubeshare_tpu.telemetry.collector import CapacityCollector
    from kubeshare_tpu.telemetry.registry import TelemetryRegistry
    registry = TelemetryRegistry()
    collector = CapacityCollector(registry, node="n0", backend="jax",
                                  lease_ttl_s=0, state_dir=str(tmp_path))
    assert collector.collect_once() and collector.last_chips == chips
    with pytest.raises(RuntimeError, match="already in use"):
        discovery.discover_chips("jax", host="n0",
                                 state_dir=str(tmp_path / "elsewhere"))


def test_real_device_without_allocator_stats_is_an_error(monkeypatch):
    """No guessed HBM for a real chip (it was 16 GiB by decree)."""
    import jax

    from kubeshare_tpu.topology import discovery

    class Dev:
        platform, device_kind, id = "tpu", "TPU v5 lite", 0

        def memory_stats(self):
            return None
    monkeypatch.setattr(jax, "local_devices", lambda: [Dev()])
    with pytest.raises(RuntimeError, match="no allocator stats"):
        discovery._jax_chips("n0")
