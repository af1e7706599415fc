"""Dispatch registry (clean fixture): every kernel module has a row,
every row resolves and names an existing parity test."""

OPS_REGISTRY = {
    "good": {
        "module": "tpuframe.ops.good_kernel",
        "symbol": "fused_good",
        "reference": "good_reference",
        "parity_test":
            "tests/test_good_kernel.py::test_fused_good_matches_reference",
    },
}
