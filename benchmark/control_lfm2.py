"""The controls of ``correct`` for the ``hybrid_moe_lm`` cells, on the
chip at the cell's own size:

    python3 benchmark/control_lfm2.py --workload lfm2_score_8k_steady \\
        --seeds 1,2,3 --which sound,fp8,no_routed,no_past_taps,kv_mod,no_qk_norm,no_bias

Each stand-in is ``reference_lfm2.forward`` with one thing changed, put
in the program's place (``drivers/serve_hybrid_lm.py``'s ``control``);
each has to read *not correct* on every seed, by at least one limit:

    fp8           every matrix product with both operands rounded to
                  float8 e4m3: the precision below the stated bfloat16
    no_routed     the routed experts left out (nothing in their place)
    no_past_taps  the convolution's two past taps dropped (w0 = w1 = 0):
                  a gate with no memory
    kv_mod        key/value head h % 8 serves query head h, in place of
                  h // 4
    no_qk_norm    the per-head norms of q and k left out
    no_bias       the expert bias left out of the choice

The command line and the printing are ``control.py``'s.
"""

from __future__ import annotations

import sys

STAND_INS = {
    "fp8": {"matmul": "fp8"},
    "no_routed": {"routed": False},
    "no_past_taps": {"past_taps": False},
    "kv_mod": {"kv_head": "mod"},
    "no_qk_norm": {"qk_norm": False},
    "no_bias": {"bias_in_choice": False},
}

if __name__ == "__main__":
    import control
    sys.exit(control.main())
