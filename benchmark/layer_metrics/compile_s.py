"""``CompileWatch``: lowering plus compile seconds, summed over the K-FAC
trainer's jitted entries. With a warm persistent cache the compile part is
the cache load; lowering is Python tracing and is paid on every run."""


def read(ctx):
    watch = ctx.run.trainer.kfac.compile_watcher()
    if watch is None or not watch.events:
        return None
    return sum(e['lowering_s'] + e['compile_s'] for e in watch.events)
