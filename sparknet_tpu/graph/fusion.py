"""Vertical fusion: which conv chains end in the fused LRN epilogue.

The executor fuses horizontally (sibling 1x1 convs,
``net.py:_detect_hfuse_groups``) and vertically: a linear
Conv -> [ReLU] -> [Pool] -> LRN chain whose tail is a 4-D
ACROSS_CHANNELS LRN runs as one block, its [ReLU+]LRN tail in the fused
epilogue op (``ops.vision.lrn_chain_epilogue``: the Pallas
one-VMEM-trip kernel on TPU, the scale-residual custom-VJP reference
elsewhere).  Nothing else is a chain: a ReLU or a pool behind a
convolution lowers to the same program inside a block as outside one.

The plan is a function of the graph alone:

- **Legality** (:func:`chain_candidates`): the statically fusable
  chains of a built ``Net`` — every intermediate blob has exactly ONE
  consumer (its own chain successor, at the right in-place version), no
  member carries a loss weight, is stateful, or needs an rng, and no
  member overlaps a horizontal-fusion group.  Violating any of these
  would change observable semantics, so such a chain is not a
  candidate.
- **Plan** (:class:`FusionPlan`, :func:`resolve_plan`): the legal
  chains with an LRN epilogue.  ``SPARKNET_FUSE=off`` is the one
  switch: per-layer execution, the parity tests' other side.

Execution stays in ``graph/net.py`` (``_apply_fused_chain``).
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import TYPE_CHECKING

from ..utils import knobs

if TYPE_CHECKING:  # pragma: no cover - typing only
    from .net import Net


@dataclasses.dataclass
class FusedChain:
    """One vertical chain: ``members[0]`` is the head Convolution, the
    rest follow in graph order, the last is the LRN.  ``epilogue`` says
    what the fused epilogue op takes: ``"relu+lrn"`` the LRN and the
    zero-slope ReLU just before it, ``"lrn"`` the LRN alone; every
    other member runs its own impl inside the block's scope."""

    members: list[str]
    epilogue: str

    def scope(self) -> str:
        return "+".join(self.members)


@dataclasses.dataclass
class FusionPlan:
    """The chains that fuse."""

    chains: list[FusedChain] = dataclasses.field(default_factory=list)

    def plan_id(self) -> str:
        """Short stable id: ``off`` when nothing fuses, else
        ``vf<N>-<hash of the member lists>`` — two runs print the same
        id iff they fused the same chains."""
        if not self.chains:
            return "off"
        canon = "|".join(sorted(";".join(c.members) for c in self.chains))
        return (f"vf{len(self.chains)}-"
                f"{hashlib.sha1(canon.encode()).hexdigest()[:8]}")

# member grammar after the head Convolution, in required order (ReLU
# and Pooling optional, at most one of each; the LRN ends the chain)
_STAGE_ORDER = ("ReLU", "Pooling", "LRN")


def _member_legal(node) -> bool:
    """Whether the node can join a chain: one bottom, one top, no state,
    no rng in either phase, no loss weight."""
    return (len(node.bottoms) == 1 and len(node.tops) == 1
            and not getattr(node.impl, "has_state", False)
            and not node.impl.needs_rng(node.lp, True)
            and not node.impl.needs_rng(node.lp, False)
            and not any(node.loss_weights()))


def _lrn_has_epilogue(net: "Net", node) -> bool:
    """Whether this LRN can run as the fused epilogue op: 4-D and
    ACROSS_CHANNELS (a WITHIN_CHANNEL LRN is an AVE pool, not a window
    over channels)."""
    region = str(node.lp.sub("lrn_param").get("norm_region",
                                              "ACROSS_CHANNELS"))
    shape = net.blob_shapes.get(node.bottoms[0])
    return (region == "ACROSS_CHANNELS" and shape is not None
            and len(shape) == 4)


def _relu_foldable(node) -> bool:
    """Zero-slope ReLU folds into the LRN epilogue kernel; a leaky
    slope keeps its own (still in-block) elementwise op."""
    return float(node.lp.sub("relu_param").get("negative_slope", 0.0)) == 0.0


def chain_candidates(net: "Net") -> list[FusedChain]:
    """Every legal LRN-tailed chain in ``net``, in graph order.

    Legality (each rule keeps fused semantics identical to per-layer
    execution):

    - head is a single-bottom/single-top ``Convolution`` that is not a
      member of a horizontal 1x1-sibling group (hfuse owns those);
    - successors follow the Conv -> [ReLU] -> [Pooling] -> LRN grammar,
      and the LRN is 4-D ACROSS_CHANNELS;
    - every intermediate top has exactly ONE consumer — the next chain
      member — *at the produced in-place version* (a blob re-read after
      an in-place rewrite is a different tensor; the version map is the
      same discipline hfuse uses).  Single-consumer also guarantees the
      intermediate is not a net output, so skipping its blob assignment
      in the fused run is observationally safe;
    - no member is stateful, stochastic, or loss-weighted.
    """
    hfused: set[str] = set()
    if getattr(net, "_hfuse_enabled", False):
        for members in getattr(net, "_hfuse_first", {}).values():
            hfused.update(m.lp.name for m in members)

    # versioned consumer map: (blob, version) -> consumer node indices
    ver: dict[str, int] = dict.fromkeys(net.input_blobs, 0)
    consumers: dict[tuple[str, int], list[int]] = {}
    produced_ver: dict[int, dict[str, int]] = {}   # node idx -> top vers
    for i, node in enumerate(net.nodes):
        for b in node.bottoms:
            consumers.setdefault((b, ver.get(b, 0)), []).append(i)
        produced_ver[i] = {}
        for t in node.tops:
            ver[t] = ver.get(t, 0) + 1
            produced_ver[i][t] = ver[t]

    chains: list[FusedChain] = []
    for i, node in enumerate(net.nodes):
        if (node.lp.type != "Convolution" or node.lp.name in hfused
                or not _member_legal(node)):
            continue
        members = [node]
        stage = -1   # index into _STAGE_ORDER consumed so far
        cur_i = i
        while members[-1].lp.type != "LRN":
            top = members[-1].tops[0]
            cons = consumers.get((top, produced_ver[cur_i][top]), [])
            if len(cons) != 1:
                break
            nxt = net.nodes[cons[0]]
            if (nxt.lp.type not in _STAGE_ORDER
                    or _STAGE_ORDER.index(nxt.lp.type) <= stage
                    or not _member_legal(nxt)):
                break
            members.append(nxt)
            stage = _STAGE_ORDER.index(nxt.lp.type)
            cur_i = cons[0]
        tail = members[-1]
        if tail.lp.type != "LRN" or not _lrn_has_epilogue(net, tail):
            continue
        prev = members[-2]      # an LRN tail has at least the head before it
        folds = prev.lp.type == "ReLU" and _relu_foldable(prev)
        chains.append(FusedChain(
            members=[m.lp.name for m in members],
            epilogue="relu+lrn" if folds else "lrn"))
    return chains


def resolve_plan(net: "Net") -> FusionPlan:
    """Read ``SPARKNET_FUSE`` (latched at Net construction, like the
    hfuse toggle — flipping the env after the first jitted step could
    never retrace the cached executable) and build the plan: unset ->
    the LRN-tailed chains of the graph; ``off`` -> no chains (per-layer
    execution).  Anything else is a typo and must not silently change
    what executes."""
    env = (knobs.raw("SPARKNET_FUSE") or "").strip()
    if env == "off":
        return FusionPlan()
    if env:
        raise ValueError(
            f"SPARKNET_FUSE={env!r}: the only value is 'off' (unset "
            f"fuses the graph's LRN-tailed chains)")
    return FusionPlan(chains=chain_candidates(net))
