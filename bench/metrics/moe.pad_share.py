"""Share of the rows ``nmc_expert_matmul`` computed in the traced steps
that no token was routed to: each held expert's buffer has a row for
every token of the call, and only the routed ones carry weight.  From the
expert layer's counters (rows routed to each held expert, per program
and layer); a run whose program has none reads nothing."""


def read(ctx):
    computed = ctx.facts.get("expert_rows_computed")
    if not computed:
        return None
    return 100.0 * (computed - ctx.facts["expert_rows_routed"]) / computed
