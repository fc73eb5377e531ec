"""How many pyramid levels of the train step compute their correlation
window on the fly (the Mosaic kernels on the chip) instead of looking it
up in a materialised volume, from the program's ``wcp_levels_windowed``
note: taken while the step traces, kept with the stored executable, and
carried by the step's ``compile`` event when this run traced it and by the
``aot`` event that holds its executable when it came from the store.
Nothing where the program says nothing (a program from before the note)."""
from . import _wcp


def read(run):
    said = _wcp.notes(run)
    if said is None or _wcp.LEVELS not in said:
        return None
    return float(said[_wcp.LEVELS])
