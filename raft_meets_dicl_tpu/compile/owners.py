"""Whose instruction is it: the phase of the model behind every device
operation of a compiled program.

A profiler capture names device operations by their compiled instruction
(``fusion.3196``, ``copy.412``) and nothing else; the compiled text
carries, on most instructions, the name stack they were traced under
(``metadata={op_name="jit(step)/transpose(jvp(RaftModule))/while/body/
closed_call/lookup/.../dot_general"}``): ``jax.named_scope`` names, flax
module paths, ``jvp(`` against ``transpose(jvp(``. :func:`parse` reads
that text once per executable (``Program._emit``) into one record: every
instruction that runs as an operation of its own, keyed as a capture
shows it, with a phase of the fixed vocabulary below, the innermost
scope of the table that named it, and a direction.

The table maps scope names to phases; models keep their own names
(``pyramid``, ``matching/mnet``, ``wcp``) and say nothing else. A scope
that is not in the table is transparent (``level0``, flax modules).
"""

import re
import time
from collections import Counter

# the phases every model's step is read by, in the order of a step
PHASES = ("input", "encoders", "corr", "lookup", "update", "up8", "loss",
          "optimizer")
# the phases of a coarse-to-fine ladder without a recurrence (DICL), beside
# ``encoders``, ``lookup`` and ``up8``: what it does before a level's
# matching and after it. No other model states their scopes
LADDER_PHASES = ("warp", "context")

# scope name -> phase. ``matching/sampler`` is two scopes to the name
# stack; the innermost one found is the owner's scope
SCOPES = {
    "input": "input",           # wire decode, on-device augmentation
    "encoders": "encoders",     # feature and context encoders, norms
    "corr": "corr",             # built once a step for the look-ups
    "pyramid": "corr",
    "lookup": "lookup",         # what each iteration does for its costs
    "matching": "lookup",
    "sampler": "lookup",
    "mnet": "lookup",
    "dap": "lookup",
    "wcp": "lookup",
    "update": "update",         # motion encoder, GRU, flow head
    "warp": "warp",             # coarse flow 2x up, frame two's features warped
    "context": "context",       # context network refining a level's flow
    "up8": "up8",               # mask head, convex combine, bilinear 2x
    "loss": "loss",
    "optimizer": "optimizer",   # everything after the gradient
}

# what the partitioner put in, not the model: every instruction whose opcode
# is a collective, whatever name stack it inherited from its operand (a
# gradient's all-reduce carries the backward convolution's, the pair
# concatenation's all-to-all the encoder's). A one-chip program holds none.
COLLECTIVE = "collective"
_COLLECTIVE_OPS = ("all-reduce", "all-gather", "all-to-all",
                   "reduce-scatter", "collective-permute",
                   "collective-broadcast")

OTHER = "other"         # named by the program, by no scope of the table
UNOWNED = "unowned"     # named by nothing the rules can reach

# what never runs as an operation of its own: not in the record
_FREE = frozenset(("parameter", "constant", "get-tuple-element", "tuple",
                   "bitcast", "after-all", "partition-id", "replica-id"))
# of those, the ones a value passes through on its way to its user
_SEE_THROUGH = frozenset(("get-tuple-element", "tuple", "bitcast"))
# containers: their bodies' instructions are the operations
_CONTAINERS = frozenset(("while", "call", "conditional"))
_NO_OPERATION = _FREE | _CONTAINERS

_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$", re.M)
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=(\s.*)$")
_OPCODE = re.compile(r"[\s)}]([a-z][a-z0-9\-]*)\(")
_RESULT = re.compile(r"\(*([a-z0-9]+\[[0-9,]*\])")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NAME = re.compile(r"%?([A-Za-z_][\w.\-]*)")
# attributes that name a computation whose instructions are operations
_CALLED = re.compile(r"(?:body|condition|to_apply|calls|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def key_of(name, rest):
    """``name:dtype[dims]`` of an instruction, from its name and the text
    after ``=``: what a capture's event text gives too (the first array
    of the result type; nothing where the result holds none)."""
    m = _RESULT.match(rest)
    return f"{name}:{m.group(1) if m else ''}"


def owner_of(op_name):
    """``(phase, scope, direction)`` an ``op_name`` says. The innermost
    scope of the table wins; a name stack that holds none gives
    ``other`` and its outermost own component (the model's class, or
    the primitive of an operation traced outside every scope)."""
    direction = "bwd" if "transpose(" in op_name else "fwd"
    first = None
    found = None
    for part in op_name.split("/"):
        inner = part.rsplit("(", 1)[-1].rstrip(")")
        if inner in SCOPES:
            found = inner
        elif first is None and not part.startswith(("jit(", "pjit(")):
            first = inner
    if found is not None:
        return SCOPES[found], found, direction
    return OTHER, first or "", direction


def _base(opcode):
    return opcode.removesuffix("-start").removesuffix("-done")


def _is_collective(opcode):
    return _base(opcode) in _COLLECTIVE_OPS


def _wrapped_collective(text, spans, called):
    """The collective an ``async-start`` / ``-done`` wraps, if it wraps
    one: the opcode of its called computation's root."""
    if not called or called[0] not in spans:
        return None
    start, end = spans[called[0]]
    for line in text[start:end].split("\n"):
        if line.lstrip().startswith("ROOT "):
            op = _OPCODE.search(line.split(" = ", 1)[-1])
            if op and _is_collective(op.group(1)):
                return _base(op.group(1))
    return None


def _computations(text):
    """``{name: (start, end)}`` spans of the computations' bodies, and
    the entry's name."""
    spans, entry = {}, None
    heads = list(_HEADER.finditer(text))
    for i, m in enumerate(heads):
        end = heads[i + 1].start() if i + 1 < len(heads) else len(text)
        spans[m.group(2)] = (m.end(), end)
        if m.group(1):
            entry = m.group(2)
    return spans, entry


def _commonest(text, span):
    """The commonest owner among a fused computation's instructions."""
    counts = Counter(owner_of(n) for n in
                     _OP_NAME.findall(text, span[0], span[1]))
    if not counts:
        return None
    # named phases before ``other``: a fusion of a phase's arithmetic
    # with one unscoped broadcast is the phase's
    return max(counts, key=lambda owner: (owner[0] != OTHER, counts[owner]))


def parse(text):
    """The owners of one compiled program, from ``compiled.as_text()``.

    For every instruction of every computation that runs its
    instructions as operations (the entry, while bodies and conditions,
    called and branch computations; not fused, applied or asynchronously
    wrapped ones) a key and an owner, resolved in this order, the rule
    that fired kept as a count:

    - ``collective``: an instruction whose opcode is a collective
      (``all-reduce``, ``all-gather``, ``all-to-all``, ``reduce-scatter``,
      ``collective-permute``, their ``-start`` / ``-done`` halves) is of
      the phase ``collective`` whatever its name stack, its scope the
      opcode;
    - ``own``: the instruction's own ``op_name``;
    - ``fusion``: for a fusion without one, the commonest owner among the
      instructions of the computation it calls;
    - ``user``, ``producer`` (``inferred``): for one still without (the
      compiler's own copies, prefetches, allocations), the owner its users
      in the same computation agree on, else its producers: a layout copy
      belongs to what it feeds. A chain resolves from its far end;
    - ``first_user``, ``first_producer`` (``inferred``): where they do
      not agree (a weight prefetched for the forward and the backward
      convolution), the first owned user in the schedule's order;
    - else ``unowned``.

    An instruction whose own name stack holds no scope of the table (a
    scan's slices of its saved iterates, its sums of weight gradients)
    takes the phase its users, else its producers, agree on, and stays
    ``other`` where they do not.

    Returns ``{"module", "owners": {phase: {scope: {direction: [key]}}},
    "inferred_keys": [key], "instructions", "inferred", "unowned",
    "rules": {rule: count}, "seconds"}``.
    """
    t0 = time.perf_counter()
    module = re.match(r"HloModule\s+([\w.\-]+)", text)
    spans, entry = _computations(text)

    # the computations whose instructions are device operations
    todo, executed = [entry] if entry else [], []
    instrs = {}     # computation -> [[name, opcode, key, owner, rule, operands]]
    rules = Counter()
    while todo:
        comp = todo.pop()
        if comp in instrs or comp not in spans:
            continue
        executed.append(comp)
        rows = instrs[comp] = []
        start, end = spans[comp]
        for line in text[start:end].split("\n"):
            m = _INSTR.match(line)
            if m is None:
                continue
            name, rest = m.groups()     # ``rest`` keeps its leading blank
            op = _OPCODE.search(rest)
            opcode = op.group(1) if op else ""
            meta = _OP_NAME.search(rest)
            # a name stack has levels; a parameter's ``op_name``, and that
            # of a copy of one, is its argument's name
            owner = owner_of(meta.group(1)) \
                if meta and opcode not in _NO_OPERATION \
                and "/" in meta.group(1) else None
            rule = "own" if owner else None
            called = _CALLED.findall(rest)
            wrapped = _wrapped_collective(text, spans, called) \
                if opcode.startswith("async") else None
            if wrapped or _is_collective(opcode):
                # the opcode decides, not the name stack: the direction is
                # still the operand's (a gradient's reduce is ``bwd``)
                owner = (COLLECTIVE, wrapped or _base(opcode),
                         owner[2] if owner else "fwd")
                rule = "collective"
            elif opcode == "fusion" or opcode.startswith("async"):
                # an asynchronous slice's wrapped computation is, like a
                # fused one, no sequence of operations of its own
                if owner is None and called and called[0] in spans:
                    owner = _commonest(text, spans[called[0]])
                    rule = "fusion" if owner else None
            elif opcode in _CONTAINERS:
                todo.extend(called)
                for group in _BRANCHES.findall(rest):
                    todo.extend(_NAME.findall(group))
            # operands: the names between the opcode's parentheses
            args = rest[op.end():] if op else ""
            cut = args.find("), ")
            operands = _NAME.findall(args if cut < 0 else args[:cut])
            key = None if opcode in _NO_OPERATION \
                else key_of(name, rest[1:])
            rows.append([name, opcode, key, owner, rule, operands])

    # users and producers inside each computation, for what is left
    inferred_keys = []
    owners = {}
    n_instr = 0
    for comp in executed:
        rows = instrs[comp]
        by_name = {r[0]: r for r in rows}
        users = {}
        for r in rows:
            r[5] = [by_name[o] for o in r[5] if o in by_name]
            for o in r[5]:
                users.setdefault(o[0], []).append(r)
        pending = [r for r in rows
                   if r[3] is None and r[1] not in _NO_OPERATION]
        # a chain of copies resolves from its far end, so in passes; what
        # no pass of agreeing neighbours reaches takes the first owned
        # user in the schedule's order, else the first owned producer
        agree = True
        while pending:
            left = []
            for r in pending:
                got = _neighbours(r, users, "user", agree) \
                    or _neighbours(r, users, "producer", agree)
                if got:
                    r[3], r[4] = got
                else:
                    left.append(r)
            if len(left) == len(pending):
                if not agree:
                    break
                agree = False
            else:
                agree = True
            pending = left
        # named, but by no scope of the table (a scan's own slices and
        # sums, a broadcast traced between two scopes): the phase its
        # neighbours agree on, if they do
        weak = [r for r in rows if r[3] is not None and r[3][0] == OTHER]
        for _ in range(3):
            moved = False
            for r in weak:
                if r[3][0] != OTHER:
                    continue
                for side in ("user", "producer"):
                    got = _neighbours(r, users, side, True)
                    if got and got[0][0] != OTHER:
                        r[3], r[4] = got
                        moved = True
                        break
            if not moved:
                break
        for name, opcode, key, owner, rule, _ in rows:
            if opcode in _NO_OPERATION:
                continue
            n_instr += 1
            if owner is None:
                owner, rule = (UNOWNED, "", "fwd"), "unowned"
            rules[rule] += 1
            if rule not in ("own", "fusion", "collective", "unowned"):
                inferred_keys.append(key)
            phase, scope, direction = owner
            owners.setdefault(phase, {}).setdefault(scope, {}).setdefault(
                direction, []).append(key)

    return {
        "module": module.group(1) if module else None,
        "owners": owners,
        "inferred_keys": inferred_keys,
        "instructions": n_instr,
        "inferred": len(inferred_keys),
        "unowned": rules["unowned"],
        "rules": dict(rules),
        "seconds": round(time.perf_counter() - t0, 4),
    }


def _walk(row, users, side, depth, found):
    """Into ``found``: the owners of the instructions on one side of
    ``row``, None for one that has none yet, looking through the free
    instructions between (a bitcast, a tuple element)."""
    for n in (users.get(row[0], ()) if side == "user" else row[5]):
        if n[3] is not None and n[3][0] == COLLECTIVE:
            # a collective moves a value, it does not own its neighbours:
            # they take what lies on its other side
            if depth:
                _walk(n, users, side, depth - 1, found)
        elif n[3] is not None:
            found.append(n[3])
        elif n[1] in _SEE_THROUGH and depth:
            _walk(n, users, side, depth - 1, found)
        elif n[1] not in _NO_OPERATION:
            # (a parameter, a constant, a loop as a whole have no say)
            found.append(None)


def _neighbours(row, users, side, agree):
    """``(owner, rule)`` from the instructions on one side of ``row``
    (its users, or its producers): the one owner they all have, or with
    ``agree`` off the first owner met in the schedule's order."""
    found = []
    _walk(row, users, side, 4, found)
    if agree:
        if found and None not in found and len(set(found)) == 1:
            return found[0], side
        return None
    first = next((o for o in found if o is not None), None)
    return (first, f"first_{side}") if first else None


def flat(record):
    """``{key: (phase, scope, direction)}`` of a record."""
    out = {}
    for phase, scopes in record["owners"].items():
        for scope, directions in scopes.items():
            for direction, keys in directions.items():
                for key in keys:
                    out[key] = (phase, scope, direction)
    return out
