"""deliver_device_ms.storm: device ms per cycle of the superstep's
delivery phases (cycle.scan, cycle.descent), in the cold-start cells
(no client traffic). The reduction is in bench/harness/phases.py."""
from harness.phases import deliver_device_ms as read  # noqa: F401
