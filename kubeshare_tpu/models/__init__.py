"""Workload model zoo.

One module per reference eval workload (``/root/reference/test/**``):
``mnist`` (north-star benchmark), ``cifar10``, ``lstm``, ``resnet``,
``vgg`` — plus ``transformer``, the long-context causal-LM family the
TPU build adds (dense or mixture-of-experts FFN, pluggable attention:
dense / Pallas flash / sequence-parallel ring), and ``lfm2``, a hybrid
decoder driven by one configuration object and a per-layer list of
block kinds (gated short convolution / grouped-query attention, dense
gated MLP / top-k routed experts of which a stated share is held).
``minicpm_sala`` (block-sparse softmax attention and decayed linear
attention by a per-layer list, muP scalings, an untied head) is on the
same pattern but FORWARD ONLY (``init``, ``apply``, ``score_fn``):
``get_model`` finds it, and it is not in ``MODEL_NAMES``, whose members
train. Each of those exposes
``init(key)``, ``loss_fn(params, batch)``,
``batch_fn(key)`` and a ``python -m kubeshare_tpu.models.<name> --steps N``
CLI; ``common.run_training`` provides the timed loop with the isolation
gate hook.
"""

MODEL_NAMES = ("mnist", "cifar10", "lstm", "resnet", "vgg", "transformer",
               "tinymlp", "lfm2")
#: served, never trained: no ``loss_fn``, no backward
FORWARD_ONLY = ("minicpm_sala",)


def get_model(name: str):
    """Return the model module for *name* (lazy import keeps jax out of
    control-plane processes)."""
    import importlib

    if name not in MODEL_NAMES + FORWARD_ONLY:
        raise ValueError(f"unknown model {name!r}; have "
                         f"{MODEL_NAMES + FORWARD_ONLY}")
    return importlib.import_module(f".{name}", __package__)
