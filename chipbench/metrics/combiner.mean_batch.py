"""Mean operations per combined batch that PCScheduler handed to the
executor in the window (``PCScheduler.batches``)."""
LAYER = "serving/scheduler.py PCScheduler"
SOURCE = "program_counter"
MOVES = "ops_per_s"
UNIT = "ops"


def read(window):
    b = window.batches
    return sum(b) / len(b) if b else None
