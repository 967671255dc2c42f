"""Zero-touch attach shim — the LD_PRELOAD equivalent.

The node agent (≙ the hook-init initContainer installing libgemhook to a
hostPath, ``docker/kubeshare-gemini-hook-init/Dockerfile:27-28``) puts
this directory on the workload container's PYTHONPATH; Python imports
``sitecustomize`` automatically at interpreter startup, before any
workload code runs. With no kubeshare env present this is a no-op, so the
shim is safe to install globally.

Failure policy: when the env REQUESTS an attach and it cannot be made,
the process must DIE (SystemExit propagates through site.py) — a pod
silently running unmetered after a transient manager/proxy outage is an
isolation breach, and the reference's LD_PRELOAD contract has the same
shape (a missing hook library fails the exec, it never silently skips
interception). Kubernetes restarts the pod until its manager answers.
Processes without kubeshare env are untouched (attach_if_env no-ops).
"""

import os


def _attach_requested() -> bool:
    # Env names are HARDCODED (mirroring kubeshare_tpu/constants.py): the
    # shim must not depend on the package it guards — if kubeshare_tpu
    # itself is broken/unimportable on the node, this check still has to
    # work so the pod dies instead of running unmetered.
    if os.environ.get("KUBESHARE_TPU_ATTACH", "").lower() == "off":
        return False
    return bool(os.environ.get("KUBESHARE_TPU_CHIP_PROXY_PORT")
                or os.environ.get("KUBESHARE_TPU_POD_MANAGER_PORT")
                or os.environ.get("KUBESHARE_TPU_VISIBLE_CHIPS"))


try:
    from kubeshare_tpu.attach import attach_if_env

    attach_if_env()
except SystemExit:
    raise  # attach.py's own fail-closed paths (bad chip grant, gang)
except Exception:
    import sys
    import traceback

    print("kubeshare-tpu attach shim failed:", file=sys.stderr)
    traceback.print_exc()
    if _attach_requested():
        raise SystemExit(
            "kubeshare-tpu: attach was requested by the pod's env but "
            "failed — refusing to run unmetered (fix the node's pod "
            "manager / chip proxy; the pod will restart)")