"""Work counts, from the algorithm and the shapes alone.

A count says what the model or the algorithm needs, never which
operations the program happens to emit, so a later change may fuse or
replace a kernel without changing what is counted.  A multiply-add is
two operations.
"""
from __future__ import annotations


def afto_cut_space(config: dict):
    """(P, D) of a polytope of the engine configuration: D counts the
    coefficients of z1, z2, z3 and every worker's x2 and x3 blocks."""
    c, h = config["problem"], config["hyper"]
    n_w, d, hid = c["n_workers"], c["n_features"], c["hidden"]
    n_rem = c["n_samples"] - int(c["n_samples"] * c["test_frac"])
    n_tr = (n_rem - int(n_rem * c["val_frac"])) // n_w
    z1 = 1
    z2 = n_w * n_tr * d
    z3 = d * hid + hid + hid + 1
    return h["p_max"], z1 + z2 + z3 + n_w * (z2 + z3)


def afto_iteration_work(config: dict, traffic: dict) -> dict:
    """Cut-matrix work of one master iteration, averaged over the refresh
    and record periods.  One pass is one product of a (P, D) polytope
    with a vector, or of a vector with it: 2 P D operations, 4 P D bytes
    of float32 coefficients read.

    - master step (Eqs. 16-21): 3 passes: the a-blocks' weighted sum for
      z, the workers' stale-weighted b-blocks, the cut values at the new
      point;
    - gap record (Eqs. 26-27): the same 3, every `record_every`;
    - refresh (Eqs. 23-25), every `t_pre`: the level-2 rollout's round
      reads the I-polytope 3 times (the cut values and their transpose
      for the z2 gradient, the cut values at the new z2); h_II's value
      and gradient run it forward and back (2 x 3 K), and the rollout
      that gives the drop rule's multipliers once more (3 K).  The
      level-3 rollout reads no polytope.

    The MLP's own operations (a few MFLOP an iteration) are not counted:
    at 197 TFLOP/s against 819 GB/s the cut bytes bind by far.

    `cut_kernel_bytes_per_iter` counts the forward cut evaluations
    alone, which the `cut_eval` kernel carries: the step's cut values,
    the record's after a refresh rewrote the polytope, and two a round
    in each of the refresh's two level-2 rollouts (the cut values at
    the old and at the new z2).  The kernels also carry the transposed
    products of the gradients, which are not counted, so the share it
    gives is a lower bound and cannot pass 100% by a miscount."""
    p, d = afto_cut_space(config)
    h = config["hyper"]
    k, t_pre, every = h["k_inner"], h["t_pre"], traffic["record_every"]
    passes = 3 + 3 / every + 9 * k / t_pre
    kernel = 1 + 1 / every + 2 * 2 * k / t_pre
    return {"flops_per_iter": passes * 2 * p * d,
            "bytes_per_iter": passes * 4 * p * d,
            "cut_kernel_bytes_per_iter": kernel * 4 * p * d}
