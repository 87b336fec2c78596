"""A cell from its configuration, its traffic mix and a seed.

A configuration (``configs/<name>.json``) gives the operator and its sizes;
a traffic mix (``traffic/<name>.json``) gives what one timed call does.
The traffic's ``kind`` names the module ``kinds/<kind>.py`` that builds the
cell: its ``build(config, traffic, seed, devices, control=...)`` returns a
``Cell``.  A new kind of call is a new module there; a new mix of a kind
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


def build(config: dict, traffic: dict, seed: int, devices: list, *,
          control: bool = False) -> Cell:
    """The cell ``config`` x ``traffic`` on ``devices``, made from ``seed``
    by ``kinds/<traffic["kind"]>.py``."""
    kind = traffic["kind"]
    path = KINDS / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"unknown traffic kind {kind!r}: no {path.name} "
                         f"in {KINDS.name}/")
    spec = importlib.util.spec_from_file_location(f"bench_kind_{kind}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.build(config, traffic, seed, devices, control=control)


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
