"""Codec and dispatch: programs JAX lowered inside the window (its
jaxpr_to_mlir_module events).  Set-up warms every shape the window
uses, so this reads 0 unless a shape escaped the warm-up."""


def read(w):
    return w["window"]["compile"]["lowerings"]
