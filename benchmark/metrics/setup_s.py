"""Process start -> window start: proxy start and backend init, the
tenants' export, compilation or cache load, weights made on the device,
the first steps."""
KIND, LAYER, UNIT, SOURCE, BETTER = "end_to_end", "", "s", "host_clock", "lower"


def read(run: dict):
    return run["setup_s"]
