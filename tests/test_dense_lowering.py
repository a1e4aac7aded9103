"""The benchmark's dense cells keep their step programs: what a PR that
rewrites the expert layers must not reach (``tests/_dense_lowering_golden.py``
says what the golden file holds and how it is made)."""

import json
import os

import pytest
from _dense_lowering_golden import DENSE_CELLS, PROGRAMS, lowered_hashes


@pytest.fixture(scope="module")
def dense_hashes():
    return {}


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("cell", DENSE_CELLS)
def test_a_dense_cell_lowers_to_the_text_it_had(cell, program, dense_hashes):
    """The benchmark's four cells without an expert layer lower to the text
    they had before PR 53 (``tests/golden_dense_lowering_pr53.json``, taken
    on its parent): the pass loops' rewrite reaches no dense model."""
    with open(os.path.join(os.path.dirname(__file__),
                           "golden_dense_lowering_pr53.json")) as f:
        golden = json.load(f)[cell][program]
    if cell not in dense_hashes:
        dense_hashes[cell] = lowered_hashes(cell)
    assert dense_hashes[cell][program] == golden
