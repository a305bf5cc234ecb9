"""Regex-driven partition rule tables: hybrid model+data sharding.

SparkNet's rounds replicate the full weight vector on every worker, so
both the τ-boundary broadcast and the resident HBM footprint scale with
total parameter bytes — and the FC layers that dominate CaffeNet/VGG
parameter counts are exactly the ones that shard cleanly along their
``num_output`` dimension.  This module is the policy half of the hybrid
scheme: an ordered rule table of ``(regex, dim)`` pairs is matched
against every parameter leaf (named ``"<layer>/<blob_idx>"``, e.g.
``"fc6/0"`` for the fc6 weight, ``"fc6/1"`` for its bias) and resolved
into a :class:`ShardPlan` — a frozen per-leaf map of which dimension
lives on the mesh's shard axis.  The trainer turns the plan into a
params-pytree of ``NamedSharding``s at init (the mechanism half lives in
``parallel/trainer.py``).

Rule semantics (first-match-wins, Caffe-style per-layer-class policy):

* rules are tried in order; the first regex that ``re.search``-matches a
  leaf name decides that leaf,
* ``dim = None`` means replicate; ``dim = k`` means shard dimension *k*
  across the plan's mesh axis,
* scalar (0-d) leaves are never partitioned, whatever the rule says,
* a matched dim that does not exist or does not divide by the shard
  count falls back to replicated — recorded in ``plan.fallbacks`` so
  the decision is auditable, never silent,
* leaves no rule matches are collected and raised loudly, all at once
  (a rule table that forgets a layer class is a bug, not a default) —
  zoo tables therefore end with an explicit catch-all.

``DEFAULT_RULES`` encodes the zoo default: FC / inner-product weight
blobs shard across chips (their ``num_output`` rows), convolutions and
all biases stay replicated + batch-sharded.  Custom tables load from a
versioned JSON file (``SPARKNET_SHARD=<path>``); an unknown version is
refused, same discipline as the checkpoint/manifest planes.

``shard_plan_id()`` is a content hash over everything that changes the
placement (axis, shard count, per-leaf dims), the same discipline as
``fuse_plan_id`` — it is stamped into perf-ledger
fingerprints and checkpoint manifests so captures from different
shardings never pool.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import re

import numpy as np

from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

RULE_TABLE_VERSION = 1

# (regex, dim) — first match wins.  FC / inner-product weight blobs
# (blob 0 of ip*/fc*/``*classifier`` layers; shape (num_output, dim_in))
# shard their output rows; everything else — convs, biases, BN state —
# replicates.  The catch-all is explicit: a table with holes raises.
DEFAULT_RULES: tuple[tuple[str, int | None], ...] = (
    (r"(^|/)(fc|ip|classifier)[^/]*/0$", 0),
    (r".*", None),
)


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Resolved placement: which dim of which leaf lives on ``axis``.

    ``dims`` maps leaf name -> sharded dimension for the sharded leaves
    only; every other leaf is replicated over ``axis``.  ``fallbacks``
    lists leaves a rule *wanted* sharded but that had to replicate
    (scalar, missing dim, or not divisible by ``n_shards``)."""

    axis: str
    n_shards: int
    table_id: str
    dims: tuple[tuple[str, int], ...]
    fallbacks: tuple[str, ...] = ()

    def __post_init__(self):
        object.__setattr__(self, "_dim_map", dict(self.dims))

    @property
    def sharded(self) -> bool:
        return bool(self.dims)

    def dim_of(self, key: str) -> int | None:
        return self._dim_map.get(key)

    def plan_id(self) -> str:
        """Content hash of the placement (``fuse_plan_id`` discipline)."""
        doc = {"axis": self.axis, "n_shards": self.n_shards,
               "dims": sorted(self.dims)}
        digest = hashlib.sha256(
            json.dumps(doc, sort_keys=True).encode()).hexdigest()[:12]
        return f"shard:{digest}"

    def dims_dict(self) -> dict[str, int]:
        return dict(self.dims)

    # -- pytree derivations ------------------------------------------------

    def _leaf_spec(self, key: str, leaf) -> P:
        dim = self.dim_of(key)
        if dim is None:
            return P()
        return P(*([None] * dim), self.axis)

    def spec_tree(self, params):
        """params-shaped pytree of PartitionSpecs (shard_map in/out specs)."""
        return {name: [self._leaf_spec(f"{name}/{i}", b)
                       for i, b in enumerate(blobs)]
                for name, blobs in params.items()}

    def sharding_tree(self, mesh: Mesh, params):
        """params-shaped pytree of NamedShardings (resolved at trainer
        init — the placement the parameters live in between rounds)."""
        return {name: [NamedSharding(mesh, self._leaf_spec(f"{name}/{i}", b))
                       for i, b in enumerate(blobs)]
                for name, blobs in params.items()}

    # -- in-shard_map helpers (exact: pure data movement) ------------------

    def gather(self, params, axis_name: str | None = None):
        """Inside a shard_map body: widen resident shards to full leaves
        via tiled all_gather (bit-exact — no arithmetic)."""
        ax = axis_name or self.axis
        out = {}
        for name, blobs in params.items():
            row = []
            for i, b in enumerate(blobs):
                dim = self.dim_of(f"{name}/{i}")
                if dim is None:
                    row.append(b)
                else:
                    row.append(lax.all_gather(b, ax, axis=dim, tiled=True))
            out[name] = row
        return out

    def take_shard(self, params, axis_name: str | None = None):
        """Inside a shard_map body: slice this position's own shard out
        of full leaves (bit-exact — no arithmetic)."""
        ax = axis_name or self.axis
        idx = lax.axis_index(ax)
        out = {}
        for name, blobs in params.items():
            row = []
            for i, b in enumerate(blobs):
                dim = self.dim_of(f"{name}/{i}")
                if dim is None:
                    row.append(b)
                else:
                    size = b.shape[dim] // self.n_shards
                    row.append(lax.dynamic_slice_in_dim(
                        b, idx * size, size, axis=dim))
            out[name] = row
        return out


def shard_plan_id(plan: ShardPlan | None) -> str:
    """Ledger/manifest stamp; ``"dp"`` is pure data parallelism (the
    historical default every committed capture predating plans carries)."""
    return plan.plan_id() if plan is not None else "dp"


def load_rule_table(path: str) -> tuple[tuple[tuple[str, int | None], ...], str]:
    """Load a versioned JSON rule table; returns (rules, table_id).

    Format::

        {"version": 1,
         "rules": [{"pattern": "(^|/)fc[^/]*/0$", "dim": 0},
                   {"pattern": ".*", "dim": null}]}

    Unknown versions are refused loudly (forward-compat discipline:
    better to stop than to silently mis-place a model)."""
    with open(path) as f:
        doc = json.load(f)
    version = doc.get("version")
    if version != RULE_TABLE_VERSION:
        raise ValueError(
            f"rule table {path}: version {version!r} != supported "
            f"{RULE_TABLE_VERSION} — refusing to guess its semantics")
    rules = []
    for i, r in enumerate(doc.get("rules", [])):
        pat, dim = r.get("pattern"), r.get("dim")
        if not isinstance(pat, str) or not (dim is None or isinstance(dim, int)):
            raise ValueError(f"rule table {path}: rule #{i} malformed: {r!r}")
        re.compile(pat)   # surface bad regexes at load, not first match
        rules.append((pat, dim))
    if not rules:
        raise ValueError(f"rule table {path}: no rules")
    digest = hashlib.sha256(
        json.dumps(rules, sort_keys=True).encode()).hexdigest()[:12]
    return tuple(rules), f"table:{digest}"


def match_partition_rules(rules, params, n_shards: int):
    """Apply an ordered rule table to a WeightCollection.

    Returns ``(dims, fallbacks, unmatched)`` over leaf names:
    ``dims[name] = k`` for sharded leaves, ``fallbacks`` for leaves a
    rule matched with a dim that could not be honored, ``unmatched`` for
    leaves no rule decided."""
    compiled = [(re.compile(pat), dim) for pat, dim in rules]
    dims: dict[str, int] = {}
    fallbacks: list[str] = []
    unmatched: list[str] = []
    for name in sorted(params):
        for i, leaf in enumerate(params[name]):
            key = f"{name}/{i}"
            for rx, dim in compiled:
                if rx.search(key) is None:
                    continue
                if dim is not None:
                    shape = tuple(leaf.shape)
                    if (len(shape) == 0 or dim >= len(shape)
                            or shape[dim] % n_shards):
                        fallbacks.append(key)
                    else:
                        dims[key] = dim
                break
            else:
                unmatched.append(key)
    return dims, fallbacks, unmatched


def resolve_plan(mode: str, params, *, axis: str, n_shards: int,
                 ) -> ShardPlan | None:
    """Resolve the ``SPARKNET_SHARD`` / ``TrainerConfig.shard`` knob into
    a plan against concrete parameter shapes (``jax.eval_shape`` structs
    work too — only ``.shape`` is consulted).

    ``""``/``"off"`` or a single-shard axis -> ``None`` (pure DP, the
    pre-plan code path byte for byte).  ``"auto"`` -> :data:`DEFAULT_RULES`;
    anything else is a JSON rule-table path.  A table that leaves leaves
    undecided raises, listing every hole."""
    mode = (mode or "off").strip()
    if mode.lower() in ("", "off", "0", "dp"):
        return None
    if n_shards <= 1:
        return None
    if mode.lower() == "auto":
        rules, table_id = DEFAULT_RULES, f"auto-v{RULE_TABLE_VERSION}"
    else:
        rules, table_id = load_rule_table(mode)
    dims, fallbacks, unmatched = match_partition_rules(rules, params, n_shards)
    if unmatched:
        raise ValueError(
            f"partition rule table {table_id} leaves {len(unmatched)} "
            f"leaves undecided: {unmatched} — add rules (or a catch-all "
            f"'.*' -> replicate) so every placement is deliberate")
    if not dims:
        return None
    return ShardPlan(axis=axis, n_shards=n_shards, table_id=table_id,
                     dims=tuple(sorted(dims.items())),
                     fallbacks=tuple(fallbacks))


def boundary_bytes_per_chip(params, plan: ShardPlan | None,
                            n_shards: int | None = None) -> int:
    """Analytic bytes ONE chip receives at the τ-boundary to end the
    round in its resident layout (codec ``none``).

    Pure DP all-reduce leaves every chip holding the full averaged
    vector, so the per-chip landing cost is total parameter bytes; under
    a plan, sharded leaves land as 1/n tiles and only replicated leaves
    arrive in full — the broadcast shrinks by the FC shard factor."""
    n = n_shards if n_shards is not None else (plan.n_shards if plan else 1)
    total = 0
    for name, blobs in params.items():
        for i, leaf in enumerate(blobs):
            nbytes = 1
            for d in leaf.shape:
                nbytes *= int(d)
            nbytes *= np.dtype(leaf.dtype).itemsize
            if plan is not None and plan.dim_of(f"{name}/{i}") is not None:
                nbytes //= n
            total += nbytes
    return total
