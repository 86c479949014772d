"""The harness's own tests run on the CPU at tiny sizes; nothing here
loads the TPU library."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from bench.common import SRC  # noqa: E402

if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))
