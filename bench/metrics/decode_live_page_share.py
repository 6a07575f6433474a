"""decode_live_page_share: of the pages the decode steps' paged
attention walked over the window (the scheduler's `decode_pages_walked`
counter: rows x pages per row of the kernel's grid, per layer), the
share that held live context (`decode_pages_live`: the active rows'
pages), in percent.  A program without these counters reads None."""


def read(run):
    keys = ("decode_pages_walked", "decode_pages_live")
    if not all(k in run.stats0 and k in run.stats1 for k in keys):
        return None
    walked = run.stats1[keys[0]] - run.stats0[keys[0]]
    if walked <= 0:
        return None
    live = run.stats1[keys[1]] - run.stats0[keys[1]]
    return 100.0 * live / walked
