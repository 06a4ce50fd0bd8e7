"""Matmul FLOPs one NGHF update requires, from the forward FLOPs per
frame F.

  3F  per gradient-batch frame          (forward 1, backward 2)
  3F  per CG-batch frame, per curvature product, ng_iters + cg_iters of
      them                              (JVP 1, transposed JVP 2)
  1F  per CG-batch frame, per candidate evaluation: cg_iters candidates
      and the zero-update base
  1F  per CG-batch frame for the one primal forward of the CG stage

Recomputation (the trainer re-runs the forward inside every product) does
not count: this is the work the algorithm needs, not the work it does.
"""


def update_flops(F, traffic):
    opt = traffic["optimizer"]
    grad_frames = traffic["grad_batch"] * traffic["frames"]
    cg_frames = traffic["cg_batch"] * traffic["frames"]
    products = opt["ng_iters"] + opt["cg_iters"]
    evaluations = opt["cg_iters"] + 1
    return F * (3 * grad_frames
                + cg_frames * (3 * products + evaluations + 1))
