"""The controls of ``correct`` for the ``latent_moe_lm`` cells, on the
chip at the cell's own size:

    python3 benchmark/control_glm_dsa.py --workload glm52_score_8k_steady \\
        --seeds 1,2,3 --which sound,fp8,full_causal,no_routed,layer2_sets

Each stand-in is ``reference_glm_dsa.forward`` with one thing changed,
put in the program's place (``drivers/serve_lm.py``'s ``control``); each
has to read *not correct* on every seed, by at least one limit:

    fp8          every matrix product with both operands rounded to
                 float8 e4m3: the precision below the stated bfloat16
    full_causal  attention over every key s <= t in place of the
                 selected set (the selector still runs and is ignored)
    no_routed    the routed experts' part left out (router and shared
                 expert only)
    layer2_sets  the last selector's sets replaced by the first one's

The command line and the printing are ``control.py``'s.
"""

from __future__ import annotations

import sys

STAND_INS = {
    "fp8": {"matmul": "fp8"},
    "full_causal": {"attend": "causal"},
    "no_routed": {"routed": False},
    "layer2_sets": {"sets": "first"},
}

if __name__ == "__main__":
    import control
    sys.exit(control.main())
