"""1 - the union of device-op intervals over the traced window."""
KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "device", "%", "device_trace", "train_tokens_per_s"


def read(run: dict):
    trace = run.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
