"""Backend compiles inside the window (a compile, or a load from the
persistent cache: a program set-up did not warm), counted by a
``jax.monitoring`` listener in the harness."""


def read(w):
    return w.counters["compiles"]
