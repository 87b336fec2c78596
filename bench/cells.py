"""A cell from its configuration, its traffic mix and a seed.

A configuration (``configs/<name>.json``) gives the operator and its sizes;
a traffic mix (``traffic/<name>.json``) gives what one timed call does.
The traffic's ``kind`` names the module ``kinds/<kind>.py`` that builds the
cell: its ``build(config, traffic, seed, devices, control=...)`` returns a
``Cell``, and its ``FAULTS`` maps the name of each fault its cells can
have to a wrapper of the timed call that plants it (``faults``).  A new
kind of call, with its faults, is a new module there; a new mix of a kind
that exists is a new data file.

With ``control=True`` the kind puts its control, a reference computed in
the precision below the configuration's, in the program's place.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import pathlib
from typing import Any, Callable

import numpy as np

KINDS = pathlib.Path(__file__).resolve().parent / "kinds"


@dataclasses.dataclass
class Cell:
    call: Callable[[Any], Any]      # one timed call
    state: Any                      # argument of the first call
    chained: bool                   # the next call starts from the result
    counts: dict                    # algorithmic counts of one call, by name
    #: (last output, calls made in all) -> {checked number: value}; run on
    #: the host once the window has closed
    check: Callable[[Any, int], dict]
    #: () -> the compiled call's ``memory_analysis()`` in bytes, or None
    memory: Callable[[], dict | None] = lambda: None


def _kind(traffic: dict):
    """The module ``kinds/<traffic["kind"]>.py``."""
    kind = traffic["kind"]
    path = KINDS / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"unknown traffic kind {kind!r}: no {path.name} "
                         f"in {KINDS.name}/")
    spec = importlib.util.spec_from_file_location(f"bench_kind_{kind}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def build(config: dict, traffic: dict, seed: int, devices: list, *,
          control: bool = False) -> Cell:
    """The cell ``config`` x ``traffic`` on ``devices``, made from ``seed``
    by ``kinds/<traffic["kind"]>.py``."""
    return _kind(traffic).build(config, traffic, seed, devices,
                                control=control)


def faults(traffic: dict) -> dict:
    """{name: wrapper of the timed call} of every fault that a cell of
    ``traffic``'s kind can have: the kind's ``FAULTS``.  The comparison
    that decides ``correct`` has to fail each."""
    return dict(_kind(traffic).FAULTS)


# Faults of a kind whose state and output are one (N, M) array, N unknowns
# of M independent systems, each planted in the output where it is
# produced.  A chained kind donates its state to the call, so a fault that
# still reads its input runs the call on a copy.

def unchanged(call):
    """The state returned unchanged."""
    def broken(state):
        return state
    return broken


def half_batch(call):
    """Half of the systems left out: their columns keep the input."""
    import jax.numpy as jnp

    def broken(state):
        out = call(jnp.copy(state))
        half = out.shape[1] // 2
        return out.at[:, half:].set(state[:, half:])
    return broken


def altered_answer(call):
    """One answer of every system altered."""
    import jax.numpy as jnp

    def broken(state):
        out = call(jnp.copy(state))
        return out.at[out.shape[0] // 2].add(0.1)
    return broken


ARRAY_FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
                "altered_answer": altered_answer}


def columns_reader(cols: np.ndarray) -> Callable[[Any], np.ndarray]:
    """Copies the columns ``cols`` of an (N, M) device array to the host."""
    import jax
    take = jax.jit(lambda x: x[:, cols])
    return lambda x: np.asarray(take(x), np.float64)


def column_mesh(devices: list):
    """(mesh over ``devices``, sharding of an (N, M) array by columns)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices), ("batch",))
    return mesh, NamedSharding(mesh, P(None, "batch"))


def normal(key, shape, sharding):
    """Standard normal float32 array, made on the device in one call."""
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32),
                   out_shardings=sharding)(key)


def memory_of(jitted, *args) -> Callable[[], dict | None]:
    """A reader of the compiled ``jitted(*args)``'s memory analysis, from
    the arguments' shapes alone, so it can run after they are freed."""
    import jax
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        args)

    def read():
        stats = jitted.lower(*shapes).compile().memory_analysis()
        if stats is None:
            return None
        out = {k: int(getattr(stats, f"{k}_size_in_bytes"))
               for k in ("argument", "output", "alias", "temp",
                         "generated_code")}
        out["total"] = (out["argument"] + out["output"] - out["alias"]
                        + out["temp"])
        return out

    return read
