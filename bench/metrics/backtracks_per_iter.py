"""Backtrack rounds per iteration the solver ran: the program's own count
(``res.trace.backtracks``), the most rounds of any row of a shard in each
iteration, over every chunk of the run. A round is one more operator
application and one more H_s."""


def read(ctx):
    iters = sum(it for it, _ in ctx["counts"])
    if iters == 0:
        return None
    return sum(rounds for _, rounds in ctx["counts"]) / iters
