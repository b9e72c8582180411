"""react_device_ms.served: device ms per cycle of the superstep's
react phase (cycle.react: test and Send, with the threshold kernel),
in the cells with client traffic. The reduction is in
bench/harness/phases.py."""
from harness.phases import react_device_ms as read  # noqa: F401
