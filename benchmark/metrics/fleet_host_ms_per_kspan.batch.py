"""Host time of the fleet dispatch per 1000 spans: the program's own
``pack_s + decode_s + plan_fit_s`` over the window's rounds."""


def read(m):
    st = m["stats"]
    host_s = st.get("pack_s", 0.0) + st.get("decode_s", 0.0) \
        + st.get("plan_fit_s", 0.0)
    return 1000.0 * host_s / (m["spans_done"] / 1000.0)
