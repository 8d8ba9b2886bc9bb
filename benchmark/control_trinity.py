"""The controls of ``correct`` for the ``trinity_score_16k_steady`` cell,
on the chip at the cell's own size:

    python3 benchmark/control_trinity.py --workload trinity_score_16k_steady \\
        --seeds 1,2,3 --which sound,fp8,no_routed,no_shared,no_gate,gate_raw,full_rope,no_sliding_rope,no_window,window_2047,no_post_norms,no_embed_scale,no_route_scale,no_renorm,softmax,no_bias,kv_mod

Each stand-in is ``reference_trinity.forward`` with one thing changed,
put in the program's place (``drivers/serve_trinity.py``'s ``control``);
each has to read *not correct* on every seed, by at least one limit:

    fp8              every matrix product with both operands rounded to
                     float8 e4m3: the precision below the stated bfloat16
    no_routed        the routed experts left out
    no_shared        the shared expert left out
    no_gate          sigmoid(g) taken as 1: no output gate
    gate_raw         the gate's sigmoid taken of W_g x, the un-normed
                     hidden state, in the normed u's place
    full_rope        the default rotary table on the full layer, which
                     has none
    no_sliding_rope  no rotary step on the sliding layers either
    no_window        the sliding layers see every earlier key
    window_2047      ... or one key fewer than sliding_window
    no_post_norms    a branch's output added as it is (pre-norm only)
    no_embed_scale   the embedding not multiplied by sqrt(hidden_size)
    no_route_scale   the gates not multiplied by 2.826
    no_renorm        the chosen scores not divided by their sum
    softmax          a softmax over every expert in the sigmoids' place
    no_bias          the expert bias left out of the choice
    kv_mod           key/value head h % 4 serves query head h, in place
                     of h // 8

The command line and the printing are ``control.py``'s.
"""

from __future__ import annotations

import sys

STAND_INS = {
    "fp8": {"matmul": "fp8"},
    "no_routed": {"routed": False},
    "no_shared": {"shared": False},
    "no_gate": {"gate": False},
    "gate_raw": {"gate_input": "raw"},
    "full_rope": {"full_rope": True},
    "no_sliding_rope": {"sliding_rope": False},
    "no_window": {"window": None},
    "window_2047": {"window": 2047},
    "no_post_norms": {"post_norms": False},
    "no_embed_scale": {"embed_scale": False},
    "no_route_scale": {"route_scale": False},
    "no_renorm": {"renormalise": False},
    "softmax": {"scoring": "softmax"},
    "no_bias": {"bias_in_choice": False},
    "kv_mod": {"kv_head": "mod"},
}

if __name__ == "__main__":
    import control
    sys.exit(control.main())
