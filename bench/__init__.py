"""The benchmark of the ERA sampling engine on TPU: see ``bench/run.py``."""

import os

# libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes nothing
# outside its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")
