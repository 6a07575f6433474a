"""window_live_page_share: of the pages the decode steps' paged
attention walked in the sliding-window rings over the window (the
scheduler's `decode_pages_walked_w`: rows x ring pages, per window
layer), the share that held a key inside the attention window
(`decode_pages_live_w`), in percent.  A program without these counters,
or a model without window layers, reads None."""


def read(run):
    keys = ("decode_pages_walked_w", "decode_pages_live_w")
    if not all(k in run.stats0 and k in run.stats1 for k in keys):
        return None
    walked = run.stats1[keys[0]] - run.stats0[keys[0]]
    if walked <= 0:
        return None
    live = run.stats1[keys[1]] - run.stats0[keys[1]]
    return 100.0 * live / walked
