"""The controls of ``correct`` for the ``mellum2_score_16k_steady`` cell,
on the chip at the cell's own size:

    python3 benchmark/control_mellum2.py --workload mellum2_score_16k_steady \\
        --seeds 1,2,3 --which sound,fp8,no_routed,no_window,window_1023,no_yarn,no_attention_factor,kv_mod,sigmoid,no_renorm

Each stand-in is ``reference_mellum2.forward`` with one thing changed,
put in the program's place (``drivers/serve_mellum2.py``'s ``control``);
each has to read *not correct* on every seed, by at least one limit:

    fp8                   every matrix product with both operands rounded
                          to float8 e4m3: the precision below the stated
                          bfloat16
    no_routed             the routed experts left out
    no_window             the sliding layers see every earlier key
    window_1023           ... or one key fewer than sliding_window
    no_yarn               the default rotary table on the full layers
    no_attention_factor   YaRN's frequencies without its factor on cos
                          and sin
    kv_mod                key/value head h % 4 serves query head h, in
                          place of h // 8
    sigmoid               sigmoid scores in place of the softmax
    no_renorm             the chosen scores not divided by their sum

The command line and the printing are ``control.py``'s.
"""

from __future__ import annotations

import sys

STAND_INS = {
    "fp8": {"matmul": "fp8"},
    "no_routed": {"routed": False},
    "no_window": {"window": None},
    "window_1023": {"window": 1023},
    "no_yarn": {"yarn": False},
    "no_attention_factor": {"attention_factor": False},
    "kv_mod": {"kv_head": "mod"},
    "sigmoid": {"scoring": "sigmoid"},
    "no_renorm": {"renormalise": False},
}

if __name__ == "__main__":
    import control
    sys.exit(control.main())
