"""How uneven the routing was in the last step: the busiest held expert's
rows over the mean of the held experts' rows, in the expert layer where that
is largest (1 is even). From the layers' own counts, exact."""


def read(run):
    by_layer = run.counters.get("expert_rows")
    if not by_layer:
        return None
    return max(max(rows) * len(rows) / max(1, sum(rows))
               for rows in by_layer.values())
