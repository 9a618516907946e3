"""The chip benchmark's CPU rehearsal (``chipbench/tests``), collected into
this suite so that each of its tests counts on its own. It runs on this
suite's virtual CPU devices; nothing in it is a device measurement."""

from chipbench.tests.test_chipbench import *  # noqa: F401,F403
from chipbench.tests.test_spans import *  # noqa: F401,F403
