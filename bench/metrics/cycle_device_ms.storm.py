"""cycle_device_ms.storm: device ms per cycle of the superstep, in
the cold-start cells (no client traffic). The reduction is in
bench/harness/readers.py."""
from harness.readers import cycle_device_ms as read  # noqa: F401
