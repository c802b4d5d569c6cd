"""Program span on the device trace: ms per engine step of the ops whose
name stack carries neither a ``dint.<engine>.<wave>`` nor a ``part.``:
what XLA puts in on its own (the loop, copies) and whatever the program
has left unnamed, mean over devices. None where the trace has no parts
at all (every step-level op would read as unnamed there)."""
from benchmarks.part_times import unnamed_ms as read  # noqa: F401
