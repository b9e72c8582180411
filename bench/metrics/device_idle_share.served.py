"""device_idle_share.served: % of the traced window with no op on the chip, in
the cells that serve client updates. The reduction is in
bench/harness/readers.py."""
from harness.readers import device_idle_share as read  # noqa: F401
