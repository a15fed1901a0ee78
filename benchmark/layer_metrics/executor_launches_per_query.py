"""Column executor: `executor.launches` delta / queries: launches of
`executor_program` and of the range launch an `auto_date_histogram` takes
first (`compiler.auto_date_range`) a query: 1 where every request is one
launch, 1.125 where one operation of eight takes two. A program without the
counter reports nothing."""


def read(ctx):
    w = ctx["window"]
    launches = w["counters"].get("executor.launches")
    if launches is None or not w["queries"]:
        return None
    return launches / w["queries"]
