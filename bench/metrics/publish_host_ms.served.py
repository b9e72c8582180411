"""publish_host_ms.served: host ms per window in the publish, in
the cells that serve client updates. The reduction is in
bench/harness/readers.py."""
from harness.readers import publish_host_ms as read  # noqa: F401
