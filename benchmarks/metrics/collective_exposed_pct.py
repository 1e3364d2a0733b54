"""Share of the traced window a chip spent inside collective operations
(all-gather, all-reduce, ...) on its serial op line, where nothing else
computes meanwhile: the exchange that is not hidden."""


def read(context):
    seconds = context["trace"]["collective_exposed_s"]
    if not seconds:
        return None  # one chip: no exchange to read
    return 100.0 * seconds / context["trace"]["window_s"]
