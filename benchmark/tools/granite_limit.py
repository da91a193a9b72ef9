"""The two readings that the granite-4.0-h-micro configuration's
``reference.rtol`` is set between, taken on the device this runs on:
``tools/phi4flash_limit.py`` pointed at this cell (that tool reads the
cell's configuration, builder, reference and ``reference.grad_groups`` by
name and nothing of Phi-4's own, so it is run as it is).

    python3 benchmark/tools/granite_limit.py --seeds 11,12 \\
        [--low-seeds 2] [--leaves] [--tiny] [--out FILE]

For each seed: the numbers of the program's loss function differentiated
once against the float32 reference's, key by key (``loss``, ``grad_norm``,
``mamba_out_rms``, ``grad_norm_mamba_ssm``, ``grad_norm_table``,
``grad_norm_attn``; ``update_norm`` is read in the cell's own runs), and
for the first ``--low-seeds`` of them the reference with every matmul
operand rounded to ``float8_e4m3fn``, which has to come out as not
correct; ``--leaves`` gives the same distance a gradient leaf.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

CELL = "granite-4.0-h-micro.b1-t8192"


def main() -> None:
    from benchlib import manifest

    tool = manifest._load_module(os.path.join(HERE, "phi4flash_limit.py"))
    tool.CELL = CELL
    if "--out" not in sys.argv:
        sys.argv += ["--out", os.path.join(
            os.path.dirname(os.path.dirname(HERE)), "chiprun_out",
            "granite_limit.json")]
    tool.main()


if __name__ == "__main__":
    main()
