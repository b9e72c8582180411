"""output_gap_ms.storm: device-idle ms per pump inside outputs() (the
knowledge dispatch and the readback), in the cold-start cells (no
client traffic). The reduction is in bench/harness/phases.py."""
from harness.phases import output_gap_ms as read  # noqa: F401
