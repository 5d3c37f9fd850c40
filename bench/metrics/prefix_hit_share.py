"""Share of prompt tokens served from cached prefix pages, over the
admissions whose first token came inside the window (the program's
per-request ``prefix_hit_tokens``)."""


def read(run):
    adm = run.prefills(run.win0, run.win1)
    prompt = sum(p for p, _ in adm)
    if not prompt:
        return None
    return 100.0 * sum(h for _, h in adm) / prompt
