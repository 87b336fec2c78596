"""Device time per call, in ms, of the operations the program issues in
its ``repro.stencil`` scope (the explicit CN right-hand side), on the chip
that spent most on them; each operation's layer is read from its
``tf_op`` in the trace (``scopes.py``).  None where no operation carries the
scope."""

import scopes


def read(run):
    return scopes.layer_ms(run, scopes.STENCIL)
