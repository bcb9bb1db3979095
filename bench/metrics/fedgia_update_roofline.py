"""Share of the HBM roofline the fedgia_update kernel reaches, in %: the
bytes it must move per round (bench/counting.kernel_bytes, per chip)
over its device time per round times the chip's HBM bandwidth. HBM
bandwidth bounds this kernel: it does some tens of operations per 28
bytes. Nothing to read where the cell's path runs no such kernel."""


def read(r):
    t = r.kernel_s("fedgia_update")
    if t is None or r.rounds <= 0:
        return None
    need = r.kernel_bytes_per_round * r.rounds / r.chips
    return 100.0 * need / (t * r.peaks["hbm_bytes_per_s"])
