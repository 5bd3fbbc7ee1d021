"""Process start to the first timed request: imports, server start, weights,
warm-up compiles or compile-cache reads."""


def read(run: dict) -> float:
    return float(run["setup_s"])
