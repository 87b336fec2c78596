"""Names of the program's layers, as ``jax.named_scope`` scopes.

A scope adds its name to the name stack of every operation traced inside
it.  The compiled program carries that stack in each operation's metadata:
``op_name`` in the HLO, ``tf_op`` in a profiler trace's device events.  So
a profile can attribute each device operation, fused or not, to the layer
that issued it.  Scopes are trace-time metadata only: the compiled code is
the same with them or without them, and they cost nothing per call.

==========================  ==============================================
Scope                       Set in
==========================  ==============================================
``repro.stencil``           ``pde.stencil.apply_periodic_stencil``: the
                            explicit right-hand side of the CN steppers
                            and of ADI
``repro.corner_correction`` the periodic corner corrections, forward and
                            transposed: Sherman-Morrison
                            (``core.tridiag``) and Woodbury
                            (``core.penta``), whatever the backend
``repro.kernel.<name>``     each Pallas kernel dispatch in
                            ``kernels.ops``, named by its ``SweepSpec`` /
                            ``RecurrenceSpec`` or fused CN kernel
``repro.pad_cast``          the lane and sweep pads, storage-dtype casts
                            and output slices around those dispatches
==========================  ==============================================

An operation under none of them (an async copy of a constant, say) is
unscoped.
"""

STENCIL = "repro.stencil"
CORNER_CORRECTION = "repro.corner_correction"
PAD_CAST = "repro.pad_cast"


def kernel(name: str) -> str:
    """The scope of a dispatch of the Pallas kernel ``name``."""
    return f"repro.kernel.{name}"
