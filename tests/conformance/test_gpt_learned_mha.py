"""The conformance suite (`_conformance.py`) over the family `gpt-learned-mha`."""
import pytest

import _conformance as C

FAMILY = "gpt-learned-mha"


@pytest.fixture(scope="module")
def fam():
    return C.family(FAMILY)


@C.cases(FAMILY)
def test_conformance(check, fam):
    check(fam)
