"""bench.py keeps no result cache and replays nothing (with no chip it
exits non-zero and prints no number); what stays testable off the chip is
the shared cost model it re-exports."""


def test_roofline_from_xla_cost_model():
    """bench.roofline: XLA flops + bytes-accessed -> MFU ceiling. The
    on-chip artifacts self-carry whether a measured MFU is near the
    memory-bound ceiling or far from a compute-bound one."""
    from bench import roofline  # repo root on sys.path via conftest

    # v5e ridge = 197e12 / 819e9 ≈ 240.5 FLOPs/byte
    memory_bound = roofline(
        {"flops": 1e12, "bytes accessed": 1e10}, "TPU v5e", 197e12
    )
    assert memory_bound["bound"] == "memory"
    assert 0 < memory_bound["roofline_mfu_ceiling"] < 0.5
    compute_bound = roofline(
        {"flops": 1e13, "bytes accessed": 1e10}, "TPU v5e", 197e12
    )
    assert compute_bound["bound"] == "compute"
    assert compute_bound["roofline_mfu_ceiling"] == 1.0
    # unknown device / missing fields degrade to {}
    assert roofline({}, "TPU v5e", 197e12) == {}
    assert roofline({"flops": 1.0, "bytes accessed": 1.0}, "GPU", 1e12) == {}
