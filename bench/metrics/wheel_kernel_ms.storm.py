"""wheel_kernel_ms.storm: device ms per cycle of the wheel kernels, in
the cold-start cells (no client traffic). The reduction is in
bench/harness/readers.py."""
from harness.readers import wheel_kernel_ms as read  # noqa: F401
