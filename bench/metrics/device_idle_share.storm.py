"""device_idle_share.storm: % of the traced window with no op on the chip, in
the cold-start cells (no client traffic). The reduction is in
bench/harness/readers.py."""
from harness.readers import device_idle_share as read  # noqa: F401
