from benchmark.lib.by_name import scope_ms


def read(run):
    return scope_ms(run, 'hvd_update')
