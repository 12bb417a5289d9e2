"""Device time under ``tcdp.mtp`` per step (device trace): what the
multi-token-prediction module runs outside its own layers, forward and
backward: the two norms, the 2d -> d projection of [next token's embedding;
trunk output], the pre-norms, residual adds and attention projections of its
layers and its final norm.  Its layers' mixers are under their own scopes
(``attn``, ``moe``).  A program without the scope reads nothing."""

UNIT = "ms"


def read(ctx):
    if ctx.extract is None or not ctx.traced_steps:
        return None
    seconds = ctx.reduce.scope_seconds(ctx.extract, ("mtp",))
    return 1e3 * seconds / ctx.traced_steps if seconds > 0 else None
