"""Mean host time of one executor pass (a ``step_fn`` call: the structure's
host preparation, its device passes and the blocking fetch), from the
harness's span around each call that starts in the window."""
LAYER = "launch/serve.py StructureExecutor + core host path"
SOURCE = "host_clock"
MOVES = "op_p95_ms"
UNIT = "ms"


def read(window):
    d = window.pass_seconds
    return 1e3 * sum(d) / len(d) if d else None
