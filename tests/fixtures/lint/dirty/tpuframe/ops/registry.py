"""Dispatch registry (dirty fixture): one stale row.

OP003: ``symbol`` no longer defined in the module; OP002: the named
parity test file does not exist.  ``rogue_kernel`` has no row at all
(OP001).
"""

OPS_REGISTRY = {
    "listed": {
        "module": "tpuframe.ops.listed_kernel",
        "symbol": "fused_listed",
        "reference": None,
        "parity_test": "tests/test_listed.py::test_listed_matches_reference",
    },
}
