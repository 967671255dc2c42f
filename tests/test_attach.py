"""Transparent-attach tests: an unmodified JAX training script routed
through the isolation runtime by env vars alone (≙ the reference's
LD_PRELOAD zero-touch contract, pod.go:445-457)."""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from kubeshare_tpu import constants as C
from kubeshare_tpu.isolation.proxy import ChipProxy
from kubeshare_tpu.isolation.tokensched import TokenScheduler, serve

REPO = Path(__file__).resolve().parent.parent
SHIM = REPO / "kubeshare_tpu" / "_shim"


def _make_proxy():
    p = ChipProxy(scheduler=TokenScheduler(window_ms=500, base_quota_ms=30,
                                           min_quota_ms=5))
    p.serve()
    return p


@pytest.fixture
def proxy():
    p = _make_proxy()
    yield p
    p.close()


def test_attach_proxy_routes_unmodified_jit(proxy, monkeypatch):
    import jax
    import jax.numpy as jnp

    from kubeshare_tpu import attach

    real_jit = jax.jit
    attach.attach_proxy("127.0.0.1", proxy.port, "workload", 0.5, 1.0)
    try:
        # an "unmodified" training loop: plain jax.jit + python loop
        @jax.jit
        def step(w, x, y):
            loss = jnp.mean((x @ w - y) ** 2)
            g = jax.grad(lambda w: jnp.mean((x @ w - y) ** 2))(w)
            return w - 0.1 * g, loss

        rng = np.random.default_rng(0)
        w_true = rng.normal(size=(4,)).astype(np.float32)
        x = rng.normal(size=(64, 4)).astype(np.float32)
        y = (x @ w_true).astype(np.float32)
        w = np.zeros(4, np.float32)
        for _ in range(40):
            w, loss = step(w, x, y)
        # results are device-resident handles, fetched on materialization
        assert isinstance(w, attach.RemoteArray)
        assert float(loss) < 1e-2
        np.testing.assert_allclose(np.asarray(w), w_true, atol=0.05)
        sess = proxy._sessions["workload"]
        assert sess.exec_count >= 40  # every step ran ON the proxy
    finally:
        attach.detach()
    assert jax.jit is real_jit  # detach restored the real jit


def _score_shaped(jax, jnp):
    """`float(score(params, toks, np.int32(length)))`: resident params,
    two small host leaves, one float read back."""
    params = jax.jit(lambda k: {"w": jnp.cos(jnp.arange(256.0) * k)})(
        np.float32(0.1))
    score = jax.jit(lambda p, toks, n: jnp.sum(
        jnp.where(jnp.arange(toks.shape[0]) < n, p["w"][toks], 0.0)))
    toks = np.arange(32, dtype=np.int32)

    def call(i):
        value = float(score(params, (toks + i) % 256, np.int32(4 + i)))
        want = np.cos(np.arange(256.0) * np.float32(0.1))[
            ((toks + i) % 256)[:4 + i]].sum()
        assert value == pytest.approx(float(want), abs=1e-4)
    return call, 2


def _train_shaped(jax, jnp):
    """`params, opt, loss = step(params, opt, x, y); float(loss)`: the
    old state's arrays are collected at the assignment and their frees
    ride on the next step."""
    def step(params, opt, x, y):
        def loss_fn(p):
            return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)
        loss, g = jax.value_and_grad(loss_fn)(params)
        opt = {"count": opt["count"] + 1.0,
               "mu": jax.tree_util.tree_map(lambda m, g: 0.9 * m + g,
                                            opt["mu"], g)}
        params = jax.tree_util.tree_map(lambda p, m: p - 0.01 * m, params,
                                        opt["mu"])
        return params, opt, loss

    step = jax.jit(step)
    init = jax.jit(lambda k: ({"w": jnp.zeros((8,)) * k, "b": jnp.zeros(())},
                              {"count": jnp.zeros(()),
                               "mu": {"w": jnp.zeros((8,)),
                                      "b": jnp.zeros(())}}))
    state = list(init(np.float32(0.0)))
    rng = np.random.default_rng(1)
    x = rng.normal(size=(16, 8)).astype(np.float32)
    y = rng.normal(size=(16,)).astype(np.float32)
    losses = []

    def call(i):
        state[0], state[1], loss = step(state[0], state[1], x, y)
        losses.append(float(loss))          # the host read ends the step
        assert len(losses) < 2 or losses[-1] < losses[-2]
    return call, 2


@pytest.mark.parametrize("shape", [_score_shaped, _train_shaped],
                         ids=["scorer", "trainer"])
def test_a_jitted_call_under_proxy_attach_is_one_request(proxy, shape):
    """What a tenant of the benchmark does, through the shim: after
    warm-up every call is exactly one request to the proxy, with its host
    leaves inside and its result's value in the reply."""
    import gc

    import jax
    import jax.numpy as jnp

    from kubeshare_tpu import attach

    attach.attach_proxy("127.0.0.1", proxy.port, "tenant", 0.5, 1.0)
    try:
        call, leaves = shape(jax, jnp)
        call(0)
        call(1)
        gc.collect()
        sess = proxy._sessions["tenant"]
        hbm = []
        for i in range(2, 8):
            before = (sess.rpc_count, sess.exec_count,
                      sess.inline_in_total, sess.inline_out_total)
            call(i)
            assert (sess.rpc_count, sess.exec_count, sess.inline_in_total,
                    sess.inline_out_total) == (
                before[0] + 1, before[1] + 1, before[2] + leaves,
                before[3] + 1)
            hbm.append(sess.hbm_used)
        # the collected results' buffers were freed by the calls after
        assert len(set(hbm)) == 1, hbm
    finally:
        attach.detach()


def test_isolation_takes_the_real_jit_and_never_loads_attach(proxy):
    """``isolation/`` lies below ``attach.py``. With the shim attached in
    the proxy's own process, a program compiled and executed through the
    proxy never enters the shim's ``jit`` (the client's tracing and the
    proxy's AOT compile read the real one from ``utils/realjit.py``); a
    proxy with no shim never loads ``kubeshare_tpu.attach`` at all."""
    import jax

    from kubeshare_tpu import attach
    from kubeshare_tpu.isolation.client import ProxyClient

    genuine = jax.jit
    attach.attach_proxy("127.0.0.1", proxy.port, "workload", 0.5, 1.0)
    shim_jit = jax.jit
    entered = []

    def counting(*args, **kw):
        entered.append(args)
        return shim_jit(*args, **kw)

    jax.jit = counting
    try:
        assert attach.real_jit() is genuine
        with ProxyClient("127.0.0.1", proxy.port, "direct", 0.5, 1.0) as c:
            x = c.put(np.ones(4, np.float32))
            out = c.compile(lambda a: a + 1.0, x)(x)
            np.testing.assert_array_equal(c.get(out),
                                          np.full(4, 2.0, np.float32))
        assert not entered
        assert proxy.total_execs == 1
    finally:
        attach.detach()
    assert jax.jit is genuine and attach.real_jit() is genuine

    code = """
import sys
import numpy as np
from kubeshare_tpu.isolation.client import ProxyClient
from kubeshare_tpu.isolation.proxy import ChipProxy
from kubeshare_tpu.isolation.tokensched import TokenScheduler
p = ChipProxy(scheduler=TokenScheduler(500, 30, 5))
p.serve()
with ProxyClient("127.0.0.1", p.port, "c", 0.5, 1.0) as c:
    x = c.put(np.ones(4, np.float32))
    out = c.compile(lambda a: a + 1.0, x)(x)
    assert c.get(out).tolist() == [2.0] * 4
p.close()
assert "kubeshare_tpu.attach" not in sys.modules, "isolation/ loaded attach"
"""
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=str(REPO), JAX_PLATFORMS="cpu"),
        timeout=300, cwd=str(REPO))
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_attach_gate_meters_jit_calls(monkeypatch):
    import jax

    from kubeshare_tpu import attach

    sched = TokenScheduler(window_ms=500, base_quota_ms=30, min_quota_ms=5)
    server = serve(sched)
    try:
        attach.attach_gate("127.0.0.1", server.server_address[1],
                           "gated", 0.5, 1.0)
        try:
            @jax.jit
            def f(x):
                return x * 2.0

            out = f(np.float32(21.0))
            assert float(out) == 42.0  # real jit executed locally
            assert sched.core.client_count() == 1
        finally:
            attach.detach()
    finally:
        server.shutdown()
        server.server_close()
        sched.close()


def _make_step(iters):
    """A raw step fn whose device time scales with ``iters`` and whose
    jitted dispatch returns immediately (async) — the case wall-clock-only
    gate accounting under-counts."""
    import jax.numpy as jnp
    from jax import lax

    def f(x):
        def body(i, a):
            return a @ a / jnp.linalg.norm(a)
        return lax.fori_loop(0, iters, body, x)

    return f


def test_gate_charges_real_device_duration():
    """VERDICT r3 weak-6: one giant async program must not buy unlimited
    runtime for one token. The gate barriers the previous dispatch with a
    host read before charging, so the debit covers real device time —
    wall-clock-only accounting would charge only the ~0.1 ms dispatches
    (nothing reads the results inside the metered region)."""
    import time

    import jax
    import jax.numpy as jnp

    from kubeshare_tpu import attach

    sched = TokenScheduler(window_ms=120000, base_quota_ms=30000,
                           min_quota_ms=10)
    server = serve(sched)
    try:
        raw = _make_step(40)
        x = jnp.eye(800) + 0.01
        # Reference run (un-metered): honest duration of 6 async steps.
        ref = jax.jit(raw)
        np.asarray(ref(x))          # compile
        t0 = time.monotonic()
        out = x
        for _ in range(6):
            out = ref(out)
        np.asarray(out)
        ref_ms = (time.monotonic() - t0) * 1000.0
        assert ref_ms > 300, f"step too fast to discriminate: {ref_ms}"

        attach.attach_gate("127.0.0.1", server.server_address[1],
                           "asyncpod", 0.5, 1.0)
        try:
            g = jax.jit(raw)        # gated
            out = x
            for _ in range(6):
                out = g(out)        # async dispatch, nothing read here
        finally:
            attach.detach()         # gate close barriers the pending step
        used = sched.window_usage("asyncpod")
        assert used >= 0.6 * ref_ms, (used, ref_ms)
    finally:
        server.shutdown()
        server.server_close()
        sched.close()


def test_gate_longer_steps_charged_proportionally():
    """A client whose steps are ~10x longer must be charged ~10x per step
    (and so, at equal request, consume its quota in proportionally fewer
    steps). Sequential clients — no thread-contention noise."""
    import jax
    import jax.numpy as jnp

    from kubeshare_tpu import attach

    sched = TokenScheduler(window_ms=300000, base_quota_ms=60000,
                           min_quota_ms=10)
    server = serve(sched)
    x = jnp.eye(800) + 0.01
    steady = {}
    try:
        for name, iters in (("light", 4), ("heavy", 40)):
            attach.attach_gate("127.0.0.1", server.server_address[1],
                               name, 0.5, 1.0)
            try:
                g = jax.jit(_make_step(iters))
                out = g(g(x))     # compile + step 1; charged by call 2's
                #                   gate, so the snapshot below excludes
                #                   the XLA compile from the compared
                #                   steady-state charge
                u0 = sched.window_usage(name)
                for _ in range(8):
                    out = g(out)
            finally:
                attach.detach()   # final barrier: everything charged
            steady[name] = sched.window_usage(name) - u0
        ratio = steady["heavy"] / max(steady["light"], 1e-9)
        assert ratio >= 4.0, f"heavy/light charge ratio only {ratio:.2f}"
    finally:
        server.shutdown()
        server.server_close()
        sched.close()


def test_gate_hbm_cap_kills_overallocator_cotenant_survives(tmp_path):
    """VERDICT r3 missing-2: a gate-mode pod that blows past its tpu_mem
    gets a clean, attributable death (ref hook's allocation-time gpu_mem
    cap, pod.go:419-424); the co-tenant keeps acquiring tokens."""
    from kubeshare_tpu.isolation import protocol

    sched = TokenScheduler(window_ms=2000, base_quota_ms=100,
                           min_quota_ms=10)
    server = serve(sched)
    child = tmp_path / "overalloc.py"
    child.write_text("""
import sys
from kubeshare_tpu.isolation.client import HbmCap
n = [0]
def fake_stats():
    n[0] += 1
    return {"bytes_in_use": n[0] * 100_000_000}
HbmCap._device_stats = staticmethod(fake_stats)
from kubeshare_tpu import attach
import jax
jax.config.update("jax_platforms", "cpu")
attach.attach_gate("127.0.0.1", int(sys.argv[1]), "overalloc", 0.5, 1.0,
                   memory=250_000_000)
import numpy as np
@jax.jit
def f(x):
    return x * 2
for i in range(50):
    f(np.float32(i))
print("UNREACHABLE: cap never fired")
""")
    try:
        proc = subprocess.run(
            [sys.executable, str(child), str(server.server_address[1])],
            capture_output=True, text=True, timeout=120,
            env=dict(os.environ, PYTHONPATH=str(REPO)), cwd=str(REPO))
        assert proc.returncode != 0, proc.stdout
        assert "HBM cap exceeded" in proc.stderr, proc.stderr[-2000:]
        assert "tpu_mem" in proc.stderr
        assert "UNREACHABLE" not in proc.stdout
        # co-tenant: the over-allocator's death freed its registration;
        # a neighbour acquires tokens without obstruction
        import time as _t
        deadline = _t.monotonic() + 5
        while sched.core.client_count() and _t.monotonic() < deadline:
            _t.sleep(0.05)
        assert sched.core.client_count() == 0
        with protocol.Connection("127.0.0.1",
                                 server.server_address[1]) as conn:
            conn.call({"op": "register", "name": "cotenant",
                       "request": 0.5, "limit": 1.0})
            reply, _ = conn.call({"op": "acquire"})
            assert reply["quota_ms"] == 100
            conn.call({"op": "release", "used_ms": 5.0})
    finally:
        server.shutdown()
        server.server_close()
        sched.close()


def test_attach_if_env_noop_without_env(monkeypatch):
    from kubeshare_tpu import attach

    for var in (C.ENV_CHIP_PROXY_PORT, C.ENV_POD_MANAGER_PORT,
                C.ENV_ATTACH_MODE):
        monkeypatch.delenv(var, raising=False)
    assert attach.attach_if_env() == ""
    assert attach.active_mode() == ""


def test_proxy_attach_uncovered_surface_fails_loudly(proxy):
    """VERDICT r3 missing-3: pmap / accelerator devices() / accelerator
    device_put must raise an actionable error under proxy attach instead
    of silently computing on the client CPU backend (the reference's hook
    covers the whole CUDA driver API; our shim covers jit)."""
    import jax

    from kubeshare_tpu import attach

    real_pmap = jax.pmap
    real_device_put = jax.device_put
    attach.attach_proxy("127.0.0.1", proxy.port, "surface", 0.5, 1.0)
    try:
        with pytest.raises(RuntimeError, match="not supported under proxy"):
            jax.pmap(lambda x: x)
        with pytest.raises(RuntimeError, match="not supported under proxy"):
            jax.devices("tpu")
        with pytest.raises(RuntimeError, match="not supported under proxy"):
            jax.local_devices(backend="tpu")

        class FakeTpuDevice:
            platform = "tpu"

        with pytest.raises(RuntimeError, match="not supported under proxy"):
            jax.device_put(np.ones(3), FakeTpuDevice())
        # the supported subset still works
        assert jax.devices("cpu")
        cpu = jax.devices("cpu")[0]
        np.testing.assert_array_equal(
            np.asarray(jax.device_put(np.ones(3), cpu)), np.ones(3))
        np.testing.assert_array_equal(
            np.asarray(jax.device_put(np.ones(3))), np.ones(3))
    finally:
        attach.detach()
    # detach restored the real APIs
    assert jax.pmap is real_pmap
    assert jax.device_put is real_device_put
    assert jax.devices("cpu")
    assert jax.pmap(lambda x: x * 2) is not None


def test_attach_static_argnums_cached_separately(proxy):
    import jax

    from kubeshare_tpu import attach

    attach.attach_proxy("127.0.0.1", proxy.port, "statics", 0.5, 1.0)
    try:
        calls = []

        @jax.jit
        def scale(x, k=2.0):
            calls.append(1)
            return x * k

        a = scale(np.float32(3.0))
        b = scale(np.float32(3.0), k=4.0)
        # kwargs are dynamic args here (uploaded), both run remotely
        assert float(a) == 6.0
        assert float(b) == 12.0
    finally:
        attach.detach()


def _attach_env(proxy, pod_name, mode=""):
    """The injected zero-touch contract, shared by every subprocess
    attach test — one place to evolve when the contract grows."""
    extra = {
        C.ENV_CHIP_PROXY_PORT: str(proxy.port),
        C.ENV_POD_NAME: pod_name,
        C.ENV_TPU_REQUEST: "0.5",
        C.ENV_TPU_LIMIT: "1.0",
    }
    if mode:
        extra[C.ENV_ATTACH_MODE] = mode
    return dict(os.environ,
                PYTHONPATH=os.pathsep.join([str(SHIM), str(REPO)]),
                **extra)


def test_unmodified_mnist_runs_through_proxy_subprocess(proxy):
    """THE zero-touch contract: `python -m kubeshare_tpu.models.mnist`
    with only env vars set (sitecustomize shim on PYTHONPATH) trains
    through the chip proxy — no source change anywhere."""
    env = _attach_env(proxy, "mnist-pod")
    proc = subprocess.run(
        [sys.executable, "-m", "kubeshare_tpu.models.mnist", "--steps", "3"],
        capture_output=True, text=True, env=env, timeout=300, cwd=str(REPO))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "steps/s" in proc.stdout
    assert "final loss" in proc.stdout
    # the workload's executions landed on OUR proxy (2 warmup + 3 timed)
    assert proxy.total_execs >= 5
    assert "mnist-pod" not in proxy._sessions  # cleanly disconnected


@pytest.mark.slow
def test_unmodified_haiku_workload_through_proxy(proxy, tmp_path):
    """Framework-agnosticism of the zero-touch contract (the reference
    proves its hook on pytorch AND tensorflow workloads, test/mnist +
    test/tensorflow): a dm-haiku training script — foreign user code,
    not this repo's model style — attaches through env alone and trains
    on the proxy."""
    pytest.importorskip("haiku")
    script = tmp_path / "haiku_mlp.py"
    script.write_text("""
import haiku as hk
import jax
import jax.numpy as jnp
import numpy as np
import optax

def net_fn(x):
    return hk.nets.MLP([32, 1])(x)

net = hk.without_apply_rng(hk.transform(net_fn))
rng = np.random.default_rng(0)
x = rng.normal(size=(64, 8)).astype(np.float32)
y = (x.sum(axis=1, keepdims=True) * 0.5).astype(np.float32)
params = net.init(jax.random.PRNGKey(0), x)
opt = optax.adam(1e-2)
opt_state = opt.init(params)

@jax.jit
def step(params, opt_state, x, y):
    def loss_fn(p):
        return jnp.mean((net.apply(p, x) - y) ** 2)
    loss, grads = jax.value_and_grad(loss_fn)(params)
    updates, opt_state = opt.update(grads, opt_state)
    return optax.apply_updates(params, updates), opt_state, loss

first = None
for i in range(30):
    params, opt_state, loss = step(params, opt_state, x, y)
    if first is None:
        first = float(loss)
final = float(loss)
print("first", first, "final", final)
assert final < first * 0.5, (first, final)
""")
    env = _attach_env(proxy, "haiku-pod", mode="proxy")
    proc = subprocess.run([sys.executable, str(script)],
                          capture_output=True, text=True, env=env,
                          timeout=300, cwd=str(REPO))
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-3000:]
    assert "final" in proc.stdout
    assert proxy.total_execs >= 30   # every step ran ON the proxy
    assert "haiku-pod" not in proxy._sessions


@pytest.mark.slow
def test_proxy_death_kills_workload_fast_no_hang():
    """When the chip proxy dies mid-training (launcherd will respawn it),
    the attached workload must fail FAST with a clear error — never hang
    on a dead socket. Crash → restart → checkpoint-resume is the
    recovery journey; this pins its first leg."""
    import time

    p = _make_proxy()
    env = _attach_env(p, "doomed-pod", mode="proxy")
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubeshare_tpu.models.mnist",
         "--steps", "100000"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=str(REPO))
    try:
        time.sleep(15)                 # mid-compile or mid-loop
        assert proc.poll() is None, proc.stdout.read()[-2000:]
        t0 = time.monotonic()
        p.close()                      # the proxy dies under the workload
        out, _ = proc.communicate(timeout=90)
        elapsed = time.monotonic() - t0
        assert proc.returncode != 0, out[-2000:]
        assert elapsed < 60, f"workload lingered {elapsed:.0f}s on a " \
                             f"dead proxy"
    finally:
        p.close()                      # idempotent; covers early asserts
        if proc.poll() is None:
            proc.kill()
            proc.communicate()


def test_gate_mode_manager_death_fails_fast():
    """Gate-mode twin: the pod manager dying mid-run must surface as a
    prompt error at the next gated call, not a hang."""
    import jax

    from kubeshare_tpu import attach

    sched = TokenScheduler(window_ms=500, base_quota_ms=30, min_quota_ms=5)
    server = serve(sched)
    attach.attach_gate("127.0.0.1", server.server_address[1],
                       "orphan", 0.5, 1.0)
    try:
        f = jax.jit(lambda x: x * 2.0)
        assert float(f(np.float32(21.0))) == 42.0
        server.shutdown()
        server.server_close()
        sched.close()
        with pytest.raises((RuntimeError, OSError)):
            for _ in range(200):       # at most until the quota forces a
                f(np.float32(1.0))     # renew against the dead manager
    finally:
        attach.detach()


@pytest.mark.slow
def test_checkpoint_resume_through_proxy_attach(proxy, tmp_path):
    """The long-training user journey under fractional sharing: an
    unmodified workload checkpoints and crash-resumes while its params
    live on the proxy as remote handles (Orbax materializes them through
    __array__). The resumed run must do only the REMAINING steps."""
    env = _attach_env(proxy, "ckpt-pod", mode="proxy")
    ckpt = str(tmp_path / "ckpt")
    r1 = subprocess.run(
        [sys.executable, "-m", "kubeshare_tpu.models.mnist", "--steps", "4",
         "--checkpoint", ckpt, "--checkpoint-every", "2"],
        capture_output=True, text=True, env=env, timeout=300, cwd=str(REPO))
    assert r1.returncode == 0, (r1.stdout + r1.stderr)[-3000:]
    # anchored: a bare "4 steps" would also match inside "12.34 steps/s"
    assert "mnist: 4 steps in" in r1.stdout, r1.stdout
    r2 = subprocess.run(
        [sys.executable, "-m", "kubeshare_tpu.models.mnist", "--steps", "8",
         "--checkpoint", ckpt, "--checkpoint-every", "2"],
        capture_output=True, text=True, env=env, timeout=300, cwd=str(REPO))
    assert r2.returncode == 0, (r2.stdout + r2.stderr)[-3000:]
    # restored at step 4 → only the remaining 4 of 8 run
    assert "mnist: 4 steps in" in r2.stdout, r2.stdout


def test_shim_fails_closed_when_attach_requested_but_unreachable():
    """A pod whose env requests an attach must DIE when the manager /
    proxy is unreachable — silently running unmetered is an isolation
    breach (the reference's LD_PRELOAD contract likewise fails the exec
    on a missing hook, it never skips interception)."""
    env = dict(
        os.environ,
        PYTHONPATH=os.pathsep.join([str(SHIM), str(REPO)]),
        **{
            C.ENV_ATTACH_MODE: "gate",
            C.ENV_POD_MANAGER_PORT: "1",     # nothing listens here
            C.ENV_POD_NAME: "doomed",
            C.ENV_TPU_REQUEST: "1",
            C.ENV_TPU_LIMIT: "1",
        },
    )
    proc = subprocess.run(
        [sys.executable, "-c", "print('RAN UNMETERED')"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0
    assert "RAN UNMETERED" not in proc.stdout
    assert "refusing to run unmetered" in proc.stderr


def test_shim_fails_closed_even_when_package_unimportable(tmp_path):
    """The shim must not depend on the package it guards: with attach
    requested but kubeshare_tpu itself missing/broken on the node, the
    pod still dies instead of running unmetered."""
    import shutil
    shutil.copy(SHIM / "sitecustomize.py", tmp_path / "sitecustomize.py")
    env = {
        "PATH": os.environ.get("PATH", ""),
        "PYTHONPATH": str(tmp_path),          # shim only — no package
        C.ENV_ATTACH_MODE: "gate",
        C.ENV_POD_MANAGER_PORT: "1",
    }
    proc = subprocess.run(
        [sys.executable, "-c", "print('RAN UNMETERED')"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0
    assert "RAN UNMETERED" not in proc.stdout
    assert "refusing to run unmetered" in proc.stderr


def test_shim_noop_without_kubeshare_env():
    """The shim is installed globally on the node: processes without
    kubeshare env must be completely untouched."""
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join([str(SHIM), str(REPO)]))
    for var in (C.ENV_CHIP_PROXY_PORT, C.ENV_POD_MANAGER_PORT,
                C.ENV_ATTACH_MODE, C.ENV_VISIBLE_CHIPS):
        env.pop(var, None)
    proc = subprocess.run(
        [sys.executable, "-c", "print('plain python ok')"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode == 0, proc.stderr[-1000:]
    assert "plain python ok" in proc.stdout
    assert "shim failed" not in proc.stderr


def test_whole_chip_pod_sets_visible_devices(monkeypatch):
    """Whole-chip pods (no manager port) get their granted chips pinned
    via TPU_VISIBLE_DEVICES, parsed from the chip ids' per-host index."""
    from kubeshare_tpu import attach
    monkeypatch.delenv("TPU_VISIBLE_DEVICES", raising=False)
    monkeypatch.setenv(C.ENV_VISIBLE_CHIPS,
                       "TPU-v5e-host-a-2,TPU-v5e-host-a-3")
    assert attach.attach_if_env() == "visible"
    assert os.environ["TPU_VISIBLE_DEVICES"] == "2,3"


def test_whole_chip_visible_devices_not_overridden(monkeypatch):
    from kubeshare_tpu import attach
    monkeypatch.setenv("TPU_VISIBLE_DEVICES", "0")
    monkeypatch.setenv(C.ENV_VISIBLE_CHIPS, "TPU-v5e-host-a-2")
    assert attach.attach_if_env() == ""
    assert os.environ["TPU_VISIBLE_DEVICES"] == "0"


def test_unparsable_chip_grant_fails_closed(monkeypatch):
    """A malformed scheduler-written chip grant must CRASH the pod, not
    silently leave TPU_VISIBLE_DEVICES unset (which would initialize every
    chip on the host, including co-tenants' — ADVICE r3)."""
    import pytest
    from kubeshare_tpu import attach
    monkeypatch.delenv("TPU_VISIBLE_DEVICES", raising=False)
    monkeypatch.setenv(C.ENV_VISIBLE_CHIPS, "garbage-without-index-")
    with pytest.raises(SystemExit, match="refusing to start"):
        attach.attach_if_env()
    assert "TPU_VISIBLE_DEVICES" not in os.environ


def test_gate_mode_also_pins_visible_devices(monkeypatch):
    """A gate-mode pod on a multi-chip host must be confined to its
    granted chip — pinning runs for every attach mode, not only the
    whole-chip fallthrough."""
    from kubeshare_tpu import attach
    from kubeshare_tpu.isolation.tokensched import TokenScheduler, serve

    sched = TokenScheduler(window_ms=500, base_quota_ms=30, min_quota_ms=5)
    server = serve(sched)
    monkeypatch.delenv("TPU_VISIBLE_DEVICES", raising=False)
    monkeypatch.setenv(C.ENV_VISIBLE_CHIPS, "TPU-v4-host-3")
    monkeypatch.setenv(C.ENV_POD_MANAGER_PORT,
                       str(server.server_address[1]))
    monkeypatch.setenv(C.ENV_POD_NAME, "gated-pin")
    monkeypatch.setenv(C.ENV_TPU_REQUEST, "0.5")
    try:
        assert attach.attach_if_env() == "gate"
        assert os.environ["TPU_VISIBLE_DEVICES"] == "3"
    finally:
        attach.detach()
        server.shutdown()
        server.server_close()
        sched.close()


def test_gate_eager_only_workload_is_charged():
    """A gate-mode pod doing ONLY eager device compute (no jax.jit
    anywhere) must still be metered — EVERY eager op passes the token
    gate, so the token economy sees its usage and a co-tenant's share
    holds. Counted, not just charged: on jax 0.9 an eager ``jnp`` call
    is jit-wrapped and after its first call rides jit's C++ fast path,
    which a primitive-level hook never sees."""
    import jax
    import jax.numpy as jnp

    from kubeshare_tpu import attach
    from kubeshare_tpu.isolation.client import ExecutionGate

    sched = TokenScheduler(window_ms=300000, base_quota_ms=60000,
                           min_quota_ms=10)
    server = serve(sched)
    passes = []
    real_gate_call = ExecutionGate.__call__
    try:
        attach.attach_gate("127.0.0.1", server.server_address[1],
                           "eager-only", 0.5, 1.0)
        ExecutionGate.__call__ = lambda self: (passes.append(1),
                                               real_gate_call(self))[1]
        try:
            x = jnp.eye(200)
            passes.clear()
            for _ in range(20):
                x = x @ x + 1.0        # eager ops only — never jit
            assert len(passes) >= 40, \
                f"only {len(passes)} of 40 eager ops passed the gate"
            float(x[0, 0])

            # the workload's own jitted step passes the gate ONCE per
            # call (in gated_jit) and keeps jit's fast path
            step = jax.jit(lambda a: a @ a + 1.0)
            step(x)
            passes.clear()
            for _ in range(10):
                x = step(x)
            assert len(passes) == 10, passes
        finally:
            ExecutionGate.__call__ = real_gate_call
            attach.detach()            # final release charges the tail
        assert sched.window_usage("eager-only") > 0.0, \
            "eager-only workload consumed device time with zero charge"
    finally:
        server.shutdown()
        server.server_close()
        sched.close()


def test_gate_eager_metering_detached_cleanly():
    """detach() must restore the execute hook and jit's fast path — a
    leaked meter would gate every later test's eager ops against a dead
    scheduler."""
    from jax._src import pjit as _pjit
    from jax._src.interpreters import pxla as _pxla

    real_call = _pxla.ExecuteReplicated.__call__
    real_fastpath = _pjit._get_fastpath_data
    sched = TokenScheduler(window_ms=1000, base_quota_ms=100,
                           min_quota_ms=10)
    server = serve(sched)
    try:
        from kubeshare_tpu import attach
        attach.attach_gate("127.0.0.1", server.server_address[1],
                           "d", 0.5, 1.0)
        assert _pxla.ExecuteReplicated.__call__ is not real_call
        assert _pjit._get_fastpath_data is not real_fastpath
        attach.detach()
        assert _pxla.ExecuteReplicated.__call__ is real_call
        assert _pjit._get_fastpath_data is real_fastpath
    finally:
        server.shutdown()
        server.server_close()
        sched.close()


def test_gate_mem_grant_without_stats_fails_closed(tmp_path):
    """VERDICT r4 weak-2: tpu_mem > 0 on a backend with no allocator
    stats must be a clean startup failure, not a warn-once disarm."""
    sched = TokenScheduler(window_ms=2000, base_quota_ms=100,
                           min_quota_ms=10)
    server = serve(sched)
    child = tmp_path / "nostats.py"
    child.write_text("""
import sys
from kubeshare_tpu.isolation.client import HbmCap
HbmCap._device_stats = staticmethod(lambda: None)   # stats-less backend
from kubeshare_tpu import attach
import jax
jax.config.update("jax_platforms", "cpu")
attach.attach_gate("127.0.0.1", int(sys.argv[1]), "nostats", 0.5, 1.0,
                   memory=100_000_000)
print("UNREACHABLE: attach succeeded unenforced")
""")
    try:
        proc = subprocess.run(
            [sys.executable, str(child), str(server.server_address[1])],
            capture_output=True, text=True, timeout=120,
            env=dict(os.environ, PYTHONPATH=str(REPO)), cwd=str(REPO))
        assert proc.returncode != 0, proc.stdout
        assert "cannot be enforced" in proc.stderr, proc.stderr[-2000:]
        assert "UNREACHABLE" not in proc.stdout
    finally:
        server.shutdown()
        server.server_close()
        sched.close()


def test_gate_oversized_device_put_dies_before_transfer(tmp_path):
    """VERDICT r4 weak-2: a single host->device put far past the cap is
    caught by the pre-transfer charge, not after the bytes land."""
    sched = TokenScheduler(window_ms=2000, base_quota_ms=100,
                           min_quota_ms=10)
    server = serve(sched)
    child = tmp_path / "bigput.py"
    child.write_text("""
import sys
import numpy as np
from kubeshare_tpu.isolation.client import HbmCap
HbmCap._device_stats = staticmethod(lambda: {"bytes_in_use": 1_000_000})
from kubeshare_tpu import attach
import jax
jax.config.update("jax_platforms", "cpu")
attach.attach_gate("127.0.0.1", int(sys.argv[1]), "bigput", 0.5, 1.0,
                   memory=50_000_000)
jax.device_put(np.zeros(100_000_000, np.uint8))   # 100 MB > 50 MB cap
print("UNREACHABLE: transfer was allowed")
""")
    try:
        proc = subprocess.run(
            [sys.executable, str(child), str(server.server_address[1])],
            capture_output=True, text=True, timeout=120,
            env=dict(os.environ, PYTHONPATH=str(REPO)), cwd=str(REPO))
        assert proc.returncode != 0, proc.stdout
        assert "pending transfer" in proc.stderr, proc.stderr[-2000:]
        assert "UNREACHABLE" not in proc.stdout
    finally:
        server.shutdown()
        server.server_close()
        sched.close()
