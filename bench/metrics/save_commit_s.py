"""Over every save the window's steps started: the time from the saved
state being ready (the later of the save call and the outputs of the step
it saves) to its commit (the writer's ``save`` returning after the
MANIFEST rename), summed and divided by the number of saves."""


def read(run):
    commits = {s.step: s.t1 for s in run.spans.of("save_write")}
    saves = [r for r in run.saves if r["step"] >= run.warmup]
    if not saves:
        return None
    return sum(commits[r["step"]] - max(r["t_call"], run.state_ready(r["step"]))
               for r in saves) / len(saves)
