"""The mean post-hoc score of the warm frames the service scored, as a share
of its re-run bound (``ServiceStats.warm_score_sum / warm_scored``: each
score is the frame's disagreement with its prior over ``rerun_threshold *
num_disp``).  None where the service keeps no such counters."""


def read(ctx):
    st = ctx["stats"]
    scored = getattr(st, "warm_scored", 0)
    if not scored:
        return None
    return 100.0 * st.warm_score_sum / scored
