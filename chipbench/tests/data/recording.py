"""A family for the tests: the dense decoder, with every call that the harness
makes into it recorded in ``CALLS`` as ``(name, args)``.  It records one
engine attribute more than the common counters, ``cache_len``.

A test lays it out as ``families/recording.py`` beside ``dense.py`` in a copy
of the harness, with a configuration that names it: what it sees recorded is
what a new family is given, with no harness file edited."""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "chipbench_family_recording_dense", Path(__file__).with_name("dense.py"))
_dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_dense)

CALLS = []
COUNTERS = ("cache_len",)


def _recorded(name):
    fn = getattr(_dense, name)

    def call(*args):
        CALLS.append((name, args))
        return fn(*args)

    return call


program_config = _recorded("program_config")
logits = _recorded("logits")
scale = _recorded("scale")
request_flops = _recorded("request_flops")
act_elements = _recorded("act_elements")
