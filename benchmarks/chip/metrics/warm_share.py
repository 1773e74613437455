"""Share of the run's frames delivered warm: frames the service classified
warm and did not re-run cold, over every frame it classified
(``ServiceStats``, priming and window)."""


def read(ctx):
    st = ctx["stats"]
    classified = st.warm_frames + st.cold_frames
    if classified == 0:
        return None
    return 100.0 * (st.warm_frames - st.warm_reruns) / classified
