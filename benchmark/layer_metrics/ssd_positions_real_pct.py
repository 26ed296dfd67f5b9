"""Model step: positions the window's rows fed the Mamba-2 mixers over the
positions the mixers computed for them, by the rule the step program applies
(``engine.stats["ssd_positions_real"]`` over ``["ssd_positions_run"]``,
counted a row a step in ``LLMEngine._plan``): a row that feeds one position
takes one turn of the recurrence, a row that feeds more takes the block form
over the whole ``prefill_chunk``, so a 17-token tail run as a 32 block is 15
positions for nothing (and a decode row sent through the block form would be
31). A model without Mamba-2 layers has no such counter: nothing to read.
Higher is better: the rest is a prompt's last chunk. Moves ttft_p90_ms."""


def read(run):
    start, end = (run["marks"][k]["stats"] for k in ("start", "end"))
    if "ssd_positions_run" not in end:
        return None
    ran = end["ssd_positions_run"] - start.get("ssd_positions_run", 0)
    if not ran:
        return None
    return 100.0 * (end["ssd_positions_real"]
                    - start.get("ssd_positions_real", 0)) / ran
