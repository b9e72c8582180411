"""flush_gap_ms: device-idle ms per pump inside the ingest and the
flush's scatter and react dispatches, in the steady cells. The
reduction is in bench/harness/phases.py."""
from harness.phases import flush_gap_ms as read  # noqa: F401
