"""deliver_device_ms.served: device ms per cycle of the superstep's
delivery phases (cycle.scan, cycle.descent), in the cells with client
traffic. The reduction is in bench/harness/phases.py."""
from harness.phases import deliver_device_ms as read  # noqa: F401
