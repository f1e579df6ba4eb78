"""kernel_counts against the program's own block list: the megakernel's VMEM
fit gate flips exactly where the benchmark's block count says it must."""

import pytest

from benchmark import kernel_counts, peaks


# (nodes, K) of the two one-chip batch cells
@pytest.mark.parametrize("nodes,k", [(1000, 64), (300, 256)], ids=["sched1k", "autoscaled"])
def test_block_bytes_equal_the_fit_gate(nodes, k):
    from kubernetriks_tpu.ops import scheduler_kernel as sk

    budget = int(sk._SELECT_VMEM_LIMIT * 0.8)
    pods = 8
    while sk.select_commit_kernel_fits(nodes, pods + 8, k):
        pods += 8
    # `pods` is the widest pod axis the gate admits: the benchmark's count of
    # the same blocks sits under the budget there and over it one tile on.
    assert kernel_counts.megakernel_vmem_block_bytes(nodes, pods, k) <= budget
    assert kernel_counts.megakernel_vmem_block_bytes(nodes, pods + 8, k) > budget


def test_hbm_bytes_are_the_blocks_without_the_scratch():
    n, p, k, c = 1000, 2304, 64, 1250
    vmem = kernel_counts.megakernel_vmem_block_bytes(n, p, k) // 2  # one buffer of each block
    per_tile = vmem - p * 4 * kernel_counts.LANE  # minus the pod-shaped scratch
    assert kernel_counts.megakernel_hbm_bytes(c, n, p, k) == per_tile * (1280 // kernel_counts.LANE)


def test_roofline_says_which_bound():
    peak = peaks.for_device("TPU v5 lite")
    assert kernel_counts.roofline(819e9, 1.0, peak) == {"least_s": 1.0, "bound": "memory"}
    assert kernel_counts.roofline(1.0, 197e12 * 2, peak)["bound"] == "compute"
    with pytest.raises(SystemExit):
        peaks.for_device("TPU v9 imaginary")
