"""A fault that exists only across chips, and the hand tool that reads it.

    python3 perfbench/control/faults_dp.py --workload criteo256x4.train \
        --seeds 11 --seconds 6 [--modes sound,rank_left_out]

``rank_left_out``  one rank's local histogram is left out of every
    histogram reduction (the rank contributes zeros): the trees are
    grown from the other ranks' rows while every row is still routed
    and counted, so the leaves' row counts stay whole and their
    weights, values and gains lose a rank's share. What a reduction
    over the wrong replica group, or a rank that joined late, does.

The modes, the output (``chiprun_out/<out>``, one JSON line a seed and
mode) and the other faults are ``control/readings.py``'s: this file adds
its fault to that tool's table and runs it. A separate process a
reading: the fault is compiled into the grow program, which the
program caches for the process's life.
"""

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


@contextlib.contextmanager
def rank_left_out(rank=1):
    from jax import lax
    import jax.numpy as jnp
    from lightgbm_tpu.parallel import comms
    inner = comms.make_hist_psum_ef

    def make_hist_psum_ef(axis_name, *args, **kwargs):
        qm, use_ef, reduce = inner(axis_name, *args, **kwargs)
        if axis_name is None:
            return qm, use_ef, reduce

        def without_one(x, ef):
            absent = lax.axis_index(axis_name) == rank
            return reduce(jnp.where(absent, jnp.zeros_like(x), x), ef)

        return qm, use_ef, without_one

    comms.make_hist_psum_ef = make_hist_psum_ef
    try:
        yield
    finally:
        comms.make_hist_psum_ef = inner


def main(argv=None, root=None):
    from control import faults, readings
    faults.FAULTS.setdefault("rank_left_out", rank_left_out)
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--modes" not in argv:
        argv += ["--modes", "rank_left_out"]
    return readings.main(argv, root)


if __name__ == "__main__":
    main()
