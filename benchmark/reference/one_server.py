"""Plain reference of a server that holds several models
(``raft-dicl-one-server``): every request is answered as its own model alone
would answer it. So there is no mathematics here, only the dispatch of a
request to the reference of the model it named (``reference/raft.py``,
``reference/dicl.py``), each on its own tree of weights drawn from the seed.
Like the references it dispatches to, it imports nothing of the program.
"""

import importlib


def entry_of(config, model_id):
    """The configuration's entry of one model: its ``model`` (the model
    file), ``reference``, ``serve`` settings and ``control_precision``."""
    for entry in config["models"]:
        if entry["model"]["id"] == model_id:
            return entry
    raise KeyError(f"the configuration holds no model {model_id!r}: "
                   f"{[e['model']['id'] for e in config['models']]}")


def module_of(entry):
    """The reference module an entry names."""
    return importlib.import_module(f"benchmark.reference.{entry['reference']}")
