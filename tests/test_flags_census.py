"""A census of the options: every flag is read somewhere and says what it
does, and their number is pinned (ROADMAP D5; the design aim counts
options).  `test_api_surface.py::test_no_dead_flags` holds the package as
a whole to the same rule by `flag("...")` reads; this one names the flag."""

import functools
import pathlib
import re

import pytest

import paddle_tpu as paddle
from paddle_tpu._core import flags

_PKG = pathlib.Path(paddle.__file__).parent
# taken at collection: a test that sets an undeclared flag defines it
_DECLARED = sorted(flags.get_flags())


@functools.cache
def _sources():
    return {p: p.read_text() for p in _PKG.rglob("*.py")}


@pytest.mark.parametrize("name", _DECLARED)
def test_flag_is_read_outside_its_definition_and_documented(name):
    defined_in = [p for p, src in _sources().items() if re.search(
        r"define_flag\(\s*['\"]%s['\"]" % re.escape(name), src)]
    assert len(defined_in) == 1, defined_in
    readers = [p for p, src in _sources().items()
               if p != defined_in[0] and name in src]
    assert readers, f"{name} is named by no module but the one defining it"
    assert flags._FLAGS[name]["help"].strip(), f"{name} has no help string"


def test_the_number_of_flags_is_pinned():
    # The number may only fall, unless an issue names the new flag: every
    # independent option doubles the configurations tests and benchmarks
    # would have to cover.
    assert len(_DECLARED) == 37, _DECLARED
