"""Device milliseconds per answer of the ops under the program's
``jax.named_scope("repro.gram.input")``: the join of [A | b] and its zero-pad to the
fused-Gram kernel's (n_pad, d_pad) layout, made before every kernel launch. The mean
over the chips. Nothing to read: no op under the scope (a program without it)."""
import re

SCOPE = "repro.gram.input"


def in_scope(path: str, scope: str) -> bool:
    """Whether one name of an op's scope path (``tf_op``) is ``scope``, bare or inside
    a transform's wrapper: JAX writes a scope entered under ``jax.vmap`` as
    ``vmap(<scope>)``."""
    name = re.compile(r"(?:[\w-]+\()*" + re.escape(scope) + r"\)*")
    return any(name.fullmatch(part) for part in path.split("/"))


def scope_ms(ctx, scope):
    """Device ms per answer under ``scope``, the mean over the chips; None when no op has it."""
    s = ctx.summary
    ns = sum(o.end - o.start for ops in s.ops.values() for o in ops if in_scope(o.scope, scope))
    t = ns / 1e9 / len(s.ops)
    return 1000.0 * t / ctx.window.count if t > 0 else None


def read(ctx):
    return scope_ms(ctx, SCOPE)
