"""wheel_kernel_ms.served: device ms per cycle of the wheel kernels, in
the cells that serve client updates. The reduction is in
bench/harness/readers.py."""
from harness.readers import wheel_kernel_ms as read  # noqa: F401
