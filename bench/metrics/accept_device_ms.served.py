"""accept_device_ms.served: device ms per cycle of the superstep's
accept phase (cycle.accept: the dedup election and the link writes),
in the cells with client traffic. The reduction is in
bench/harness/phases.py."""
from harness.phases import accept_device_ms as read  # noqa: F401
