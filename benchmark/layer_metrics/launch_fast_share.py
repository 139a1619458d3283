"""Counter: of the calls the K-FAC trainer made of its watched step
programs over the run (``CompileWatch.dispatch_counters``, every entry of
the engine's watch, from the first warm-up step on), the share that was
handed to the executable of the entry's previous call with no fingerprint
taken, in percent. The rest walked the state and the batch a leaf at a
time in Python in front of the launch: a first call of a program, or one
the last executable rejected. ``None`` on a program whose watch does not
count its dispatches, and where nothing was called."""


def read(ctx):
    watcher = getattr(ctx.run.trainer.kfac, 'compile_watcher', None)
    watch = watcher() if callable(watcher) else None
    counters = getattr(watch, 'dispatch_counters', None)
    if counters is None:
        return None
    by_entry = counters().values()
    fast = sum(c['fast'] for c in by_entry)
    calls = fast + sum(c['fingerprinted'] for c in by_entry)
    return 100.0 * fast / calls if calls else None
