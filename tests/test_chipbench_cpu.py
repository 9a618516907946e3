"""The chip benchmark's CPU rehearsal (``chipbench/tests``), collected into
this suite so that each of its tests counts on its own. It runs on this
suite's virtual CPU devices; nothing in it is a device measurement."""

from chipbench.tests import test_spans as _spans
from chipbench.tests.test_chipbench import *  # noqa: F401,F403
from chipbench.tests.test_spans import *  # noqa: F401,F403

# A dense collective on host mirrors runs from a launch plan of its own
# (accl_tpu/device/tpu.py ``_resolve_host``), so a warmed-up acclbench.host
# window runs every launch from its plan, as the device-resident cells do:
# the rehearsal's table of cells whose launches all resolve in full
# (``PLANNED``) no longer holds it.
_spans.PLANNED.pop("acclbench.host", None)
