"""stage_device_ms.served: device ms per cycle of the superstep's
wheel, stage, probe, append and account phases, in the cells with
client traffic. The reduction is in bench/harness/phases.py."""
from harness.phases import stage_device_ms as read  # noqa: F401
