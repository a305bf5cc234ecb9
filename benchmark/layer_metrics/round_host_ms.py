"""round_host_ms: milliseconds of a round that are the host's own: the
length of ``DistributedTrainer.train_round`` less the part spent blocked on
the loss (argument checks, staging, the RNG split, the dispatch, the
bookkeeping after the fetch); the median over the rounds of the traced
window.

layer: round; unit: ms; source: program_span (self time of
``sparknet.trainer.round`` with ``sparknet.trainer.loss_fetch`` as its
child); moves: train_img_s in the round cells.  ``round_gap_ms`` times the
device's side of the same hand-over.  Absent where the driver runs no
rounds or the program has no such spans.
"""

from ..lib import program_spans


def read(cap) -> float | None:
    return program_spans.median_ms(program_spans.self_seconds(
        program_spans.load(cap), "trainer.round", ("trainer.loss_fetch",)))
