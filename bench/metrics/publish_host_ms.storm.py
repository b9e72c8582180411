"""publish_host_ms.storm: host ms per window in the publish, in
the cold-start cells (no client traffic). The reduction is in
bench/harness/readers.py."""
from harness.readers import publish_host_ms as read  # noqa: F401
