"""Trace, device 0: share of busy time in the KDA mixers OUTSIDE the delta
rule, forward and backward, in percent: the scope `mx.kda` less the scope
`mx.kda.core` under it. What is left are the mixer's products (wq, wk, wv,
wo, the low-rank pairs), the operands' preparation (short convolutions,
per-head normalisations, the decay: under `mx.kda.prepare` where the
program has that scope, under `mx.kda` itself where not) and the output
gate and norm. A program without the scopes reads nothing."""
from reduce import op_scopes

MIXER = "mx.kda"
CORE = "mx.kda.core"


def glue_share(scopes):
    """Percent of busy self time, from an `op_scopes.Scopes`; None where
    nothing ran under the mixers' scope."""
    mixer = scopes.share_of_busy(MIXER)
    if not mixer:
        return None
    return 100.0 * (mixer - scopes.share_of_busy(CORE))


def compute(ctx):
    scopes = op_scopes.of_run(ctx)
    return glue_share(scopes) if scopes else None
