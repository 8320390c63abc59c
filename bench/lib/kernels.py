"""How the program's Pallas kernels are named in a device trace.

The patterns match the names the trace shows for each kernel's events
(the op name, or its long name).  Kept in one place, so that a change to
the kernels' names is one edit here.
"""

import re

PCC_TILES = re.compile(r"pcc_tiles")
PCC_TOPK = re.compile(r"pcc_topk")
ANY_KERNEL = re.compile(f"{PCC_TILES.pattern}|{PCC_TOPK.pattern}")
