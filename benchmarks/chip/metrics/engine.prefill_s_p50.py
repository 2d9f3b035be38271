"""Engine: median seconds of one prefill dispatch, fenced on the device
(the program's ``engine.prefill_dispatch_s`` span), in the window."""


def read(ctx):
    from stats import percentile
    return percentile(ctx.hist("engine.prefill_dispatch_s"), 50)
