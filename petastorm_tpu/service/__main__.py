import os
import sys

# Service processes never take the chip: the trainer on the same host owns
# it, and a second process initializing the TPU runtime fails or hangs.
# Unconditional, like exec_in_new_process's children — pinned before any
# import can initialize a JAX backend (building a reader does).
os.environ["JAX_PLATFORMS"] = "cpu"

from petastorm_tpu.service.cli import main  # noqa: E402

sys.exit(main())
