"""Engine: median host seconds of one engine step that dispatched a decode
chunk, less the time it waited on the device (the program's
``engine.step_host_s`` histogram), in the window; None where the program
keeps no such histogram."""


def read(ctx):
    from stats import percentile
    return percentile(ctx.hist("engine.step_host_s"), 50)
