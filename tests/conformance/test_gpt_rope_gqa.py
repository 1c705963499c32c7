"""The conformance suite (`_conformance.py`) over the family `gpt-rope-gqa`."""
import pytest

import _conformance as C

FAMILY = "gpt-rope-gqa"


@pytest.fixture(scope="module")
def fam():
    return C.family(FAMILY)


@C.cases(FAMILY)
def test_conformance(check, fam):
    check(fam)
