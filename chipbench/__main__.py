import time

START = time.perf_counter()          # setup_s counts from here

import sys  # noqa: E402

from chipbench.run import main  # noqa: E402

sys.exit(main(start=START))
