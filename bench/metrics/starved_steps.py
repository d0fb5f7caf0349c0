"""Window steps whose dispatch found the step before it already done,
so that the device had run dry: the program's counter
``train.starved_dispatches`` (``Trainer.telemetry``) over those steps."""


def read(run):
    return run.starved
