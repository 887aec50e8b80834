"""`python -m lightgbm_tpu ...` = the reference CLI binary (src/main.cpp)."""

import sys

from .application import main

sys.exit(main())
