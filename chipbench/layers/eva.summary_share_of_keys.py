"""% of the keys attended that are summaries: counters serve.decode.eva.summary_rows over .eva.exact_rows + .eva.summary_rows, the window's increase (each fed slot's last real query, per layer and dispatch). 0 would mean the traffic never left the first window."""


def read(obs):
    c = obs.get("counters") or {}
    pooled = c.get("serve.decode.eva.summary_rows")
    exact = c.get("serve.decode.eva.exact_rows")
    if pooled is None or not exact:
        return None
    return 100.0 * pooled / (pooled + exact)
