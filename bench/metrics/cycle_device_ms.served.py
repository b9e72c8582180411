"""cycle_device_ms.served: device ms per cycle of the superstep, in
the cells that serve client updates. The reduction is in
bench/harness/readers.py."""
from harness.readers import cycle_device_ms as read  # noqa: F401
