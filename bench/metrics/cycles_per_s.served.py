"""cycles_per_s.served: engine cycles a second on the served path, in
the cells that serve client updates. The reduction is in
bench/harness/readers.py."""
from harness.readers import cycles_per_s as read  # noqa: F401
