import time

T_PROCESS_START = time.perf_counter()

import sys  # noqa: E402

from .harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=T_PROCESS_START))
