"""attention: of the (q block, kv block) tiles of the two-stream row's
(2L)^2 square, the share the flash kernels compute — the program's
`flash_tiles_total{mask="block_diffusion",state="live"}` over live + skipped,
counted as the step is traced, forward and backward launches together
(ops/flash_attention.py::_count_tiles). 28 % at tiles of 512 where only the
mask's support is computed (288 of 1,024); 53 % if the rule fell back to a
causal triangle over 2L; 100 % if nothing were skipped. A program that does
not count the tiles (older than the counter), or that traced no such kernel,
gives None: the metric is left out of the line."""


def read(ctx):
    try:
        from ddp_classification_pytorch_tpu.obs import spans
    except ImportError:  # the program is older than its recorder
        return None
    counted = spans.counters()

    def tiles(state):
        return counted.get(("flash_tiles_total",
                            (("mask", "block_diffusion"), ("state", state))), 0)

    live, skipped = tiles("live"), tiles("skipped")
    if not live + skipped:
        return None
    return 100.0 * live / (live + skipped)
