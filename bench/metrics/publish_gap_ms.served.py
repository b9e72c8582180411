"""publish_gap_ms.served: device-idle ms per pump inside the publish
(diff, delivery) and the pump's accounting, in the cells with client
traffic. The reduction is in bench/harness/phases.py."""
from harness.phases import publish_gap_ms as read  # noqa: F401
