"""gather_roofline: the page gather's share of its memory roofline (device
trace). Bytes it needs: each live page of each bound sequence read and
written once, plus the code words of live SECDED pages
(``counts.gather_step``); over the gather's device time at the chip's HBM
bandwidth. Padded block-table entries count nothing, so a gather that
reads only live blocks cannot pass 100%."""


def read(run):
    sec = run.layer_s("gather") if run.trace is not None else 0.0
    if not sec or not run.steps or run.peaks is None:
        return None
    c = run.counts
    need = sum(c.gather_step(run.page_bytes, run.code_bytes,
                             c.live_blocks(s["lens"], run.block_tokens)
                             * run.dims["L"], s.get("secded_pages", 0))
               for s in run.steps)
    return 100.0 * need / (sec * run.peaks["hbm_bytes_per_s"])
