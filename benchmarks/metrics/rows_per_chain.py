"""Mean rows per dispatched decode chain, from the benchmark's span around engine.decode_chain."""


def read(run, trace):
    rows = [c["rows"] for c in run["calls"] if c["kind"] == "decode_chain"]
    return sum(rows) / len(rows) if rows else None
