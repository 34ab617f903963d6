"""Share of the unified step's computed rows (max_batch x chunk a step)
that carried a token, in percent: the engine's own step counter
(``Engine.step_stats``), over every step the engine ran.  Nothing where
the engine keeps no such counter."""


def read(run):
    stats = getattr(getattr(run.rec, "engine", None), "step_stats", None)
    return None if stats is None else 100.0 * stats()["fed_row_share"]
