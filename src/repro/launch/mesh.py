"""Production mesh definition.

A function, not a module-level constant: importing this module never
touches jax device state (the dry-run sets the placeholder device count
before any jax initialization).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes.  The code places arrays with
    ``shard_map`` and sharding constraints and carries no shardings in
    its types; ``jax.make_mesh`` defaults to Explicit axes, under which
    reshapes and gathers of sharded values are refused."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; multi-pod adds a leading 2-pod axis.

    Axes: "data" carries DP+FSDP, "model" carries TP/EP, "pod" composes
    with "data" for hierarchical data parallelism (gradient reduction over
    ICI within a pod, DCN across pods).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Debug mesh over whatever devices exist (tests, examples)."""
    n = len(jax.devices())
    if model < 1 or n % model != 0:
        # a real error, not an assert: asserts vanish under ``python -O``
        raise ValueError(
            f"cannot build host mesh: {n} devices not divisible by "
            f"model={model}")
    return _make_mesh((n // model, model), ("data", "model"))


def make_filter_mesh(n_parts: int | None = None, *, data_shards: int = 1):
    """2-D ``("data", "model")`` mesh for filtering: both scaling axes.

    The paper's scalability argument (§3.5) is replication in *two*
    dimensions: profiles are spread across chips AND the document stream
    is fanned across replicas.  The software form is one mesh:

    * ``"model"`` — the query axis.  A
      :class:`repro.core.engines.base.ShardedPlan` stacks per-part tables
      on a leading axis and ``shard_map``\\ s them over ``"model"``, so
      each device advances only its slice of the subscription set.
    * ``"data"`` — the document axis.  ``filter_batch_sharded2d`` /
      ``filter_bytes_sharded2d`` partition the batch (``EventBatch`` /
      ``ByteBatch``) rows over ``"data"``, so each replica row of the
      mesh sees only its slice of the document stream.

    ``data_shards`` is a *request*: it is shrunk to the largest value
    that divides the device count, so any setting is placeable on any
    host (1 device ⇒ a ``(1, 1)`` mesh; the degenerate shapes are what
    the CI device-count matrix exercises).  The remaining devices form
    the ``"model"`` axis; ``n_parts`` (when given) shrinks that axis to
    the largest count dividing the part count — e.g. 6 parts on 4
    devices yields a 3-wide model axis, never an error.
    """
    n = len(jax.devices())
    if data_shards < 1:
        raise ValueError(f"data_shards must be >= 1, got {data_shards}")
    if n_parts is not None and n_parts < 1:
        raise ValueError(f"n_parts must be >= 1, got {n_parts}")
    data = min(int(data_shards), n)
    while n % data != 0:
        data -= 1
    model = n // data
    if n_parts is not None:
        while n_parts % model != 0:
            model -= 1
    return _make_mesh((data, model), ("data", "model"))
