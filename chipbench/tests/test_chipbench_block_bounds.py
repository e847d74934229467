"""``block_bounds_ms``: device time of BMW's block-bounds kernel per served
batch, read from a hand-made trace."""

import pytest

from metrics import block_bounds_ms, stage1_kernel_ms

# device ops (name, start ns, duration ns): the scoring kernels that
# ``stage1_kernel_ms`` reads, XLA fusions, and no block-bounds kernel
EVENTS = [["fusion.1", 100, 50], ["blockmax_score_batched.3", 120, 80],
          ["fusion.2", 400, 100], ["impact_accumulate_batched.1", 700, 100]]


def test_block_bounds_ms_reads_its_kernel_per_batch():
    """Device time of the ``bmw_block_bounds`` ops per served batch, apart
    from ``stage1_kernel_ms``'s scoring kernels; None where no such op ran
    (bounds made of XLA ops)."""
    events = EVENTS + [["bmw_block_bounds.2", 210, 30_000],
                       ["bmw_block_bounds", 520, 10_000]]
    ctx = {"events": events, "rec": {"batches": [{}, {}, {}, {}]}}
    assert block_bounds_ms.read(ctx) == pytest.approx(40_000 * 1e-6 / 4)
    assert stage1_kernel_ms.read(ctx) == pytest.approx(180 * 1e-6 / 4)
    assert block_bounds_ms.read(dict(ctx, events=EVENTS)) is None
    assert block_bounds_ms.read(dict(ctx, rec={"batches": []})) is None
