"""Control of the ``lm_train_hybrid`` cells: the cell with the selective
scan's decays, cumulative sums and carried state in bfloat16, the nearest
precision below the float32 that the configuration states for them. It has
to print ``correct: false``; its readings are the upper ones that the
limits of ``configs/<config>.json`` "check" stand under.

    python3 benchmark/controls/hybrid_bf16_scan.py --workload <cell> --seed <n> --seconds 30 --trace 0

``tpudl.zoo.lm_blocks.SCAN_DTYPE`` is what ``ssd_scan`` holds Δ·A, its
running sum inside a chunk, the decays ``L`` and the state carried from
chunk to chunk in; the products' operands are bfloat16 in the sound program
too. A running sum of 128 log-decays keeps 8 bits in bfloat16, so a decay
over a chunk is off by up to exp(±|sum| / 256), and the carried state
rounds once a chunk, 64 times a sequence.
"""

import os
import runpy
import sys

import jax.numpy as jnp

if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from tpudl.zoo import lm_blocks

    lm_blocks.SCAN_DTYPE = jnp.bfloat16
    sys.argv = [os.path.join(root, "benchmark", "run.py"), *sys.argv[1:]]
    runpy.run_path(sys.argv[0], run_name="__main__")
