"""react_device_ms.storm: device ms per cycle of the superstep's react
phase (cycle.react: test and Send, with the threshold kernel), in the
cold-start cells (no client traffic). The reduction is in
bench/harness/phases.py."""
from harness.phases import react_device_ms as read  # noqa: F401
