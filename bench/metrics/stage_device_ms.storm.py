"""stage_device_ms.storm: device ms per cycle of the superstep's
wheel, stage, probe, append and account phases, in the cold-start
cells (no client traffic). The reduction is in
bench/harness/phases.py."""
from harness.phases import stage_device_ms as read  # noqa: F401
