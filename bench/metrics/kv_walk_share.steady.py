"""Kernels: pages the paged decode kernel's walk covered in the window
(``ServeStats.kv_pages_walked``) over what a walk of every page of every
slot would cover: decode steps x slots x pages a slot x the layers that
run the kernel (%)."""


def read(run):
    steps = run.delta("decode_steps")
    if steps <= 0:
        return None
    eng = run.spec["engine"]
    pages = -(-int(eng["max_len"]) // int(eng["page_size"]))
    layers = run.counts.decode_kernels([1])["paged_decode"][2]
    full = steps * int(eng["num_slots"]) * pages * layers
    return 100.0 * run.delta("kv_pages_walked") / full
