"""Milliseconds that jax spent lowering and compiling (its own
`jaxpr_to_mlir_module` and `backend_compile` events, persistent-cache reads
included) inside the window, per solve.  Set-up warms every program the
window runs, so anything here is compiled again by the program itself."""


def read(ctx):
    if not ctx["solves"]:
        return None
    return 1e3 * ctx["compile_s"] / len(ctx["solves"])
