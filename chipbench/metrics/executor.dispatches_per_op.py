"""Device programs the executor dispatched per operation answered in the
window (difference of ``StructureExecutor.device_steps``)."""
LAYER = "launch/serve.py StructureExecutor + core host path"
SOURCE = "program_counter"
MOVES = "ops_per_s"
UNIT = "dispatches/op"


def read(window):
    return window.device_steps / window.ops if window.ops else None
