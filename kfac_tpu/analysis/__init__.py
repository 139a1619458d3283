"""kfaclint: AST + IR JAX/SPMD correctness analysis for this repo.

See docs/ANALYSIS.md for the rule table and suppression syntax; the CLI
lives at ``tools/kfaclint.py``. Importing this package populates the
rule registry (the rule modules register on import).

The AST rules (KFL001–KFL005) need only the stdlib; the drift rules
(KFL100–KFL112) import live ``kfac_tpu`` modules at *check* time; the
IR rules (KFL201–KFL205, ``analysis/ir/``) trace the engines at *check*
time — not at import time, so ``from kfac_tpu import analysis`` stays
cheap; and the pod rules (KFL301–KFL305, ``analysis/pod/``) abstractly
interpret the host control code across virtual ranks, stdlib-only like
the AST tier.
"""

from kfac_tpu.analysis import (  # noqa: F401  (imported for registration)
    drift,
    ir,
    pod,
    rules_jit,
    rules_pytree,
    rules_spmd,
)
from kfac_tpu.analysis.core import (  # noqa: F401
    Finding,
    Project,
    Rule,
    all_rules,
    analyze,
    get_rules,
    load_baseline,
    load_project,
    register,
    remap_baseline,
    render_json,
    render_text,
    save_baseline,
    split_baseline,
)

AST_RULE_CODES = ('KFL001', 'KFL002', 'KFL003', 'KFL004', 'KFL005')
PROJECT_RULE_CODES = (
    'KFL100', 'KFL101', 'KFL102', 'KFL103', 'KFL104', 'KFL107', 'KFL108',
    'KFL109', 'KFL111', 'KFL112',
)
IR_RULE_CODES = ('KFL201', 'KFL202', 'KFL203', 'KFL204', 'KFL205')
POD_RULE_CODES = ('KFL301', 'KFL302', 'KFL303', 'KFL304', 'KFL305')
