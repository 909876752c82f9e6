"""Host time of one engine step's token pull, in ms: the program's span
``mcsa.engine.token_pull`` (``np.asarray`` of the decoded tokens, where
the host waits for the decode to finish), averaged over the engine steps
of the traced window."""
from bench.program_spans import mean_ms


def read(facts):
    return mean_ms(facts, "engine.token_pull")
