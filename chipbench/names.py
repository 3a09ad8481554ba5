"""How the program's work is named in a device trace today.

The store's ``pallas_call`` has no ``name=`` and its ops carry no
``named_scope``.  The fused scan→select kernel shows as the custom call
``fused_scan_select`` (HLO instruction ``%fused_scan_select.1``), and the
rest of a search as the other ops of the ``jit_search_stacked`` program.
"""
KERNEL = "fused_scan_select"
SEARCH_MODULE = "jit_search_stacked"


def is_kernel(ev) -> bool:
    return ev.name == KERNEL or ev.name.startswith(KERNEL + ".")


def in_search(ev) -> bool:
    return ev.module == SEARCH_MODULE
