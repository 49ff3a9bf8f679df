"""The program's own sub-blocks held to a reference's, one at a time.

A family's `reference_loss` runs its float32 reference sub-block by
sub-block and calls a BlockCheck after each one with the input the
reference's sub-block had and its output (and what it read beside the
residual stream, where a layer reads another layer's arrays). The check
runs the PROGRAM's sub-block, jitted once a kind, on that input rounded to
the program's type, and records the error of the residual update,
|(program out - in) - (reference out - in)| over |reference out - in|.
Judging each sub-block on the reference's input keeps one sub-block's
error out of the next one's reading.
"""

from __future__ import annotations


class BlockCheck:
    """errors: {"<layer>.<kind>": error}.

    kind(i, name) is the label a reading of layer i's sub-layer `name` is
    keyed by (the limit's key); key(i, name, sub), where given, says which
    sub-blocks share one jitted runner (default: the kind); extra(i, name,
    extra) picks what the program's sub-block reads beside the residual
    stream out of what the reference passed (default: all of it)."""

    def __init__(self, trainer, dtype, kind, key=None, extra=None):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.parallel.functional import functional_call

        self.params, self.kind = trainer.params, kind
        self.key = key or (lambda i, name, sub: kind(i, name))
        self.extra = extra or (lambda i, name, extra: extra)
        self.errors = {}
        self._subs = {(i, name): sub for i, layer in enumerate(
            trainer.model.model.layers) for name, sub
            in layer._sub_layers.items()}
        dtype = jnp.dtype(dtype)

        def runner(sub):
            def run(h, arrays, extra):
                x = h.astype(dtype)[None]
                got = functional_call(sub, arrays, x, *(
                    e.astype(dtype)[None] for e in extra))
                if isinstance(got, (tuple, list)):
                    got = got[0]
                return (got - x)[0].astype(jnp.float32)
            return jax.jit(run)

        self._run = {}
        for (i, name), sub in self._subs.items():
            self._run.setdefault(self.key(i, name, sub), runner(sub))

        @jax.jit
        def error(update, h_in, h_out):
            want = (h_out - h_in).astype(jnp.float32)
            return jnp.linalg.norm(update - want) / jnp.linalg.norm(want)

        self._error = error

    def arrays(self, i, name):
        """The program's arrays of layer i's sub-layer `name`, by the name
        inside it."""
        pre = f"model.layers.{i}.{name}."
        return {k[len(pre):]: v for k, v in self.params.items()
                if k.startswith(pre)}

    def __call__(self, i, name, h_in, h_out, extra=()):
        run = self._run[self.key(i, name, self._subs[i, name])]
        update = run(h_in, self.arrays(i, name), self.extra(i, name, extra))
        self.errors[f"{i}.{self.kind(i, name)}"] = float(
            self._error(update, h_in, h_out))
