"""What a `fit` call costs besides its epochs: the call's length less the
epochs' own time, (last epoch end - first epoch end) x E / (E - 1)."""


def read(run):
    if run.epochs < 2 or len(run.stamps) < 2:
        return None
    epochs_s = (run.stamps[-1] - run.stamps[0]) * run.epochs / (len(run.stamps) - 1)
    return run.fit_s - epochs_s
