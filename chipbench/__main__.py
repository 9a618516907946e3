import time

T_START = time.perf_counter()  # set-up counts from here

import sys  # noqa: E402

from chipbench.run import main  # noqa: E402

sys.exit(main(t_start=T_START))
