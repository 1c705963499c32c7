"""The conformance suite (`_conformance.py`) over the family `llama-tiny`."""
import pytest

import _conformance as C

FAMILY = "llama-tiny"


@pytest.fixture(scope="module")
def fam():
    return C.family(FAMILY)


@C.cases(FAMILY)
def test_conformance(check, fam):
    check(fam)
