"""The reference's readings on both small cells, pinned bitwise: the data
files were written by the reference as it stood before the model family
moved behind ``families/``, so a change to a family's arithmetic, key
schedule or key table shows here as a changed bit."""

import json

import jax
import numpy as np
import pytest

import cells
import run
import tiny

SEED = 2**33 + 11
DATA = cells.HERE / "tests" / "data"


def _signs(tree) -> list:
    """Per leaf, the counts of positive and of negative elements."""
    return [[int(np.sum(x > 0)), int(np.sum(x < 0))] for x in map(np.asarray, jax.tree.leaves(tree))]


def readings(moe: bool, precision: str) -> dict:
    """What the comparison reads from the reference's three steps on the
    small cell: losses, leaf norms, and the signs of the first gradient and
    of the change."""
    cell = tiny.cell(moe=moe)
    pool = run.tokens.batches(SEED, 3, cell.global_rows, cell.seq, cell.config["vocab_size"])
    r = run.reference_readings(cell, SEED, pool, jax.devices(), precision=precision)
    return {"losses": r.losses, "grad_norms": np.asarray(r.grad_norms).tolist(),
            "change_norms": np.asarray(r.change_norms).tolist(),
            "grad_signs": _signs(r.first_grad), "change_signs": _signs(r.change)}


@pytest.mark.parametrize("precision", ["f32", "fp8"])
@pytest.mark.parametrize("moe", [True, False], ids=["moe", "dense"])
def test_reference_readings_are_pinned(moe, precision):
    pinned = json.loads((DATA / f"reference.tiny_{'moe' if moe else 'dense'}.json").read_text())
    assert readings(moe, precision) == pinned[precision]
