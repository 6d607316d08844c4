"""(instrument x grid combo) backtests whose jobs the dispatcher recorded
as complete inside the window, over the window's seconds."""


def read(run):
    if run.loop != "closed":
        return None
    return run.backtests / run.seconds
