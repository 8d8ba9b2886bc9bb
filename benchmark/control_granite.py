"""The controls of ``correct`` for the ``granite_score_1k_steady`` cell,
on the chip at the cell's own size:

    python3 benchmark/control_granite.py --workload granite_score_1k_steady \\
        --seeds 1,2,3 --which sound,fp8,no_carry,no_decay,no_dt_bias,no_d_skip,norm_after_gate,conv_reversed,no_conv_bias,bc_swapped,no_residual_multiplier,no_embedding_multiplier,no_logits_scaling,sqrt_scale,qk_norm,rope,kv_mod

Each stand-in is ``reference_granite.forward`` with one thing changed,
put in the program's place (``drivers/serve_granite.py``'s ``control``);
each has to read *not correct* on every seed, by at least one limit:

    fp8                      every matrix product with both operands
                             rounded to float8 e4m3: the precision below
                             the stated bfloat16
    no_carry                 no state carried across chunk boundaries:
                             each 256-token chunk starts from zero
    no_decay                 exp(dt A) taken as 1
    no_dt_bias               dt_bias left out of dt's softplus
    no_d_skip                no D x skip
    norm_after_gate          the gated norm's order swapped: N(y) * silu(z)
    conv_reversed            the conv's taps in reverse order
    no_conv_bias             the conv's bias left out
    bc_swapped               B and C swapped
    no_residual_multiplier   residual_multiplier taken as 1
    no_embedding_multiplier  embedding_multiplier taken as 1
    no_logits_scaling        the logits not divided by 8
    sqrt_scale               attention scaled by 1/sqrt(64), not 1/64
    qk_norm                  per-head q/k norms (gain 1) put back
    rope                     a rotary table on the attention layers
    kv_mod                   key/value head h % 8 serves query head h, in
                             place of h // 4

The command line and the printing are ``control.py``'s.
"""

from __future__ import annotations

import sys

STAND_INS = {
    "fp8": {"matmul": "fp8"},
    "no_carry": {"carry": False},
    "no_decay": {"decay": False},
    "no_dt_bias": {"dt_bias": False},
    "no_d_skip": {"d_skip": False},
    "norm_after_gate": {"gated_norm": "after"},
    "conv_reversed": {"conv_taps": "reversed"},
    "no_conv_bias": {"conv_bias": False},
    "bc_swapped": {"swap_bc": True},
    "no_residual_multiplier": {"residual_multiplier": False},
    "no_embedding_multiplier": {"embedding_multiplier": False},
    "no_logits_scaling": {"logits_scaling": False},
    "sqrt_scale": {"attention_scale": "sqrt"},
    "qk_norm": {"qk_norm": True},
    "rope": {"rope": True},
    "kv_mod": {"kv_head": "mod"},
}

if __name__ == "__main__":
    import control
    sys.exit(control.main())
