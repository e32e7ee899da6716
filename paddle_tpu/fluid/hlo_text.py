"""An optimized HLO module's text (``Compiled.as_text()``), read with regular
expressions: the one parser of the tree.  ``fluid.trace.executable_record``
keeps ``op_rows`` of a lane's own executable; ``tools/compile_for_v5e.py``
reads a described chip's compile with the same functions.

In a scheduled module (``is_scheduled=true``) the order of a computation's
lines is the order the device runs them in, and an operation's name
(``fusion.937``, ``copy-done.35``) is the name a device trace of the same
executable prints.  XLA keeps an instruction's ``op_name`` (the
``jax.named_scope`` path it was traced under) through its passes, inside a
fused computation too, but the operations the compiler makes itself carry
none: memory-space assignment's prefetches (``copy-start`` / ``copy-done``,
``slice-start`` / ``slice-done``), layout copies, the bitcasts and reshapes
between them.  ``op_rows`` gives each of those the Fluid op it works for.
"""

import re

from ..ops.registry import STEP_SCOPE

__all__ = ['NO_BUFFER', 'MOVES', 'DONE', 'mb', 'operations', 'operands',
           'op_name_of', 'fluid_scope', 'op_rows']

# results that alias or only group other results
NO_BUFFER = ('parameter', 'tuple', 'get-tuple-element', 'bitcast', 'while',
             'conditional', 'call', 'copy-start', 'optimization-barrier')
# results that hold their operand's values, whole or a slice, elsewhere
MOVES = ('bitcast', 'copy-start', 'copy-done', 'slice-start', 'slice-done')
# the done half of an asynchronous pair, where the device waits for it: the
# common ones (any ``<opcode>-done`` counts; ``op_rows`` names a generic
# ``async-done`` by the operation its computation wraps, as XLA's own short
# form does: a v5e prints memory-space assignment's sliced prefetches long)
DONE = ('copy-done', 'slice-done', 'all-reduce-done', 'all-gather-done',
        'collective-permute-done', 'async-done')
_ASYNC = ('async-start', 'async-update', 'async-done')

_FLUID_SCOPE = re.compile(r'^[A-Za-z_][A-Za-z0-9_]*\..+$')
_WRAPPED = re.compile(r'^[A-Za-z_][A-Za-z0-9_]*\((.*)\)$')   # jvp(...), ...
_HEAD = re.compile(r'(ENTRY )?%?([\w.\-]+) \(.*\{$')
_OP = re.compile(
    r'\s+(ROOT )?%?([\w.\-]+) = (.*?)\s([a-z][a-z\-]*)\((.*)$')
_CALLED = re.compile(
    r'\b(?:body|condition|true_computation|false_computation|to_apply|calls)'
    r'=%?([\w.\-]+)|\bbranch_computations=\{([^}]*)\}')
# the operations whose called computations run as operations of their own
# on the device (a reducer's ``to_apply`` and what an asynchronous pair
# wraps do not: the pair's two halves are what runs)
_CALLERS = ('while', 'conditional', 'call')


def mb(shape):
    """MB of the arrays an HLO shape text names (``pred``: a byte)."""
    total = 0.0
    for kind, bits, dims in re.findall(r'\b([a-z]+?)(\d*)\[([\d,]*)\]', shape):
        size = int(bits or 8) / 8e6
        for d in filter(None, dims.split(',')):
            size *= int(d)
        total += size
    return total


def _lines(hlo):
    """(computation, is ENTRY, name, shape text, opcode, the rest of the
    line, is ROOT) of every operation of the module, in the order of the
    text."""
    where = entry = None
    for line in hlo.splitlines():
        head = _HEAD.match(line)
        if head:
            entry, where = bool(head.group(1)), head.group(2)
            continue
        op = _OP.match(line)
        if op:
            yield (where, entry) + op.groups()[1:] + (bool(op.group(1)), )


def operations(hlo):
    """(computation, is ENTRY, name, shape text, opcode, the rest of the
    line) of each operation outside the fused computations, in the order
    of the text: the schedule's, in an optimized module."""
    fused = set(re.findall(r' fusion\(.*?calls=%?([\w.\-]+)', hlo))
    return (row[:6] for row in _lines(hlo) if row[0] not in fused)


def operands(rest):
    """The names an operation reads: what stands before the first ``)``."""
    return re.findall(r'%([\w.\-]+)', rest.split(')')[0])


def op_name_of(rest):
    found = re.search(r'op_name="([^"]*)"', rest)
    return found.group(1) if found else None


def fluid_scope(op_name):
    """The innermost path element of a Fluid op's form (``<op type>.<first
    output>``) under the step's scope, or None: the rule a device trace's
    reader applies to ``tf_op`` (``chipbench/scopes.py:fluid_scope``).  JAX
    wraps the elements of a transformed trace (``transpose(jvp(mul.fc_0
    .tmp_0))``); the wrappers are taken off first."""
    inside, scope = False, None
    for element in (op_name or '').rstrip(':').split('/'):
        while True:
            m = _WRAPPED.match(element)
            if not m:
                break
            element = m.group(1)
        if element == STEP_SCOPE:
            inside = True
        elif inside and _FLUID_SCOPE.match(element):
            scope = element
    return scope


def _pair(opcode):
    """Either half of an asynchronous pair."""
    return opcode.endswith(('-start', '-done'))


def _through(row):
    """What an owner or a carried value is looked for through: MOVES, the
    pairs' other kinds, and the operations that only regroup values."""
    opcode = row['opcode']
    return opcode in MOVES or _pair(opcode) or opcode in (
        'get-tuple-element', 'tuple') or (
            opcode == 'custom-call'
            and 'custom_call_target="ConcatBitcast"' in row['rest'])


def op_rows(hlo):
    """``{operation name: row}`` for every operation outside the fused
    computations of ENTRY and of the computations it runs (loop bodies and
    conditions, branches, calls), under the name a device trace prints:

      opcode, computation, mb (of its result), op_name
      scope    the Fluid op of its own ``op_name`` (``fluid_scope``)
      inside   a ``fusion``'s or ``call``'s: the sorted Fluid-op scopes of
               the instructions of the computation it calls (XLA fuses a
               parameter's update into the product that makes its
               gradient; the fusion carries its root's scope alone)
      owner    ``scope``; else, for the done half of an asynchronous pair
               (``DONE``), the owner of the first reader of its result in
               the schedule, looked for through the operations that only
               move or regroup a value: the op that waits.  For any other
               operation without a scope: the one scope ``inside`` it,
               else its first reader's owner, else (a root) the owner of
               what made its first operand.  None where no rule reaches a
               scope
      moves    an asynchronous pair's: what it carries, an ENTRY parameter's
               name (``state_rw__...``) or the owner of the operation that
               made it, followed back through the same operations and from
               a loop's body into the loop's operand

    A name that two computations both use (XLA names are the module's, so
    this should not happen) keeps both rows under ``rows`` and owns
    nothing."""
    comps, scopes_in, roots, wrapped, entry_name = {}, {}, {}, {}, None
    for where, entry, name, shape, opcode, rest, root in _lines(hlo):
        row = {'name': name, 'opcode': opcode, 'computation': where,
               'shape': shape, 'rest': rest, 'op_name': op_name_of(rest),
               'inside': [], 'calls': opcode in _CALLERS}
        if opcode in _ASYNC:
            # ``slice-start.3 = ... async-start(%x), calls=%wrapped`` and
            # ``slice-done.3 = ... async-done(%slice-start.3)``: named by
            # the operation the pair wraps, as the short form
            # ``slice-done(...)`` is (callees come first in the text, a
            # start before its done)
            called = re.search(r'\bcalls=%?([\w.\-]+)', rest)
            wraps = roots.get(called.group(1)) if called else wrapped.get(
                (operands(rest) + [None])[0])
            if wraps:
                wrapped[name] = wraps
                row['opcode'] = wraps + opcode[len('async'):]
        row['scope'] = fluid_scope(row['op_name'])
        comps.setdefault(where, []).append(row)
        if row['scope']:
            scopes_in.setdefault(where, set()).add(row['scope'])
        if root:
            roots[where] = opcode
        if entry:
            entry_name = where
    # the computations the device runs operation by operation, and the
    # loop each body belongs to
    run, todo, loop_of = {}, [entry_name], {}
    while todo:
        where = todo.pop()
        if where in run or where not in comps:
            continue
        run[where] = {'by_name': {r['name']: r for r in comps[where]},
                      'readers': {}}
        for row in comps[where]:
            row['args'] = operands(row['rest'])
            for arg in row['args']:
                run[where]['readers'].setdefault(arg, []).append(row)
            called = [one or many for one, many in
                      _CALLED.findall(row['rest'])]
            if row['opcode'] in ('fusion', 'call') and called:
                row['inside'] = sorted(scopes_in.get(called[0], ()))
            if row['calls']:
                for callee in re.findall(r'[\w.\-]+', ' '.join(called)):
                    todo.append(callee)
                    if row['opcode'] == 'while':
                        loop_of[callee] = (where, row)

    def own(row):
        if row['scope']:
            return row['scope']
        return row['inside'][0] if len(row['inside']) == 1 else None

    def forward(where, row, seen):
        """The owner of ``row``'s first reader: of the op that waits."""
        for reader in run[where]['readers'].get(row['name'], ()):
            if reader['name'] not in seen:
                seen.add(reader['name'])
                if reader['calls'] and not _pair(reader['opcode']):
                    return own(reader)   # a whole loop or branch reads it
                found = None if _through(reader) else own(reader)
                return found or forward(where, reader, seen)
        return None

    def backward(where, row, seen):
        """The owner of what made ``row``'s first operand."""
        made = run[where]['by_name'].get((row['args'] + [None])[0])
        if made is None or made['name'] in seen:
            return None
        seen.add(made['name'])
        return own(made) or backward(where, made, seen)

    def carried(where, name, seen):
        """What the value ``name`` is: an ENTRY parameter's name, or the
        owner of the operation that made it."""
        made = run[where]['by_name'].get(name)
        if made is None or (where, name) in seen:
            return None
        seen.add((where, name))
        if made['opcode'] == 'parameter':
            return name if where == entry_name else None
        if made['opcode'] == 'get-tuple-element' and where in loop_of:
            whole = run[where]['by_name'].get(made['args'][0])
            index = re.search(r'index=(\d+)', made['rest'])
            if whole and whole['opcode'] == 'parameter' and index:
                # a loop's body: element ``index`` of the loop's operand
                outer, loop = loop_of[where]
                init = run[outer]['by_name'].get(loop['args'][0])
                if init and init['opcode'] == 'tuple' \
                        and int(index.group(1)) < len(init['args']):
                    return carried(outer, init['args'][int(index.group(1))],
                                   seen)
                return None
        if own(made) and not _through(made):
            return own(made)
        return carried(where, made['args'][0], seen) if made['args'] \
            else None

    for where in run:
        for row in comps[where]:
            row['owner'] = own(row) or forward(where, row, {row['name']}) \
                or backward(where, row, {row['name']})
    out = {}
    for where in sorted(run, key=list(comps).index):
        for row in comps[where]:
            pair = _pair(row['opcode']) and row['args']
            kept = {'opcode': row['opcode'], 'computation': where,
                    'mb': mb(row['shape']), 'op_name': row['op_name'],
                    'scope': row['scope'], 'inside': row['inside'],
                    'owner': row['owner'],
                    'moves': carried(where, row['args'][0], set())
                    if pair else None}
            before = out.get(row['name'])
            if before is not None:
                both = (before.get('rows') or [before]) + [kept]
                kept = dict(dict.fromkeys(kept), opcode=row['opcode'],
                            inside=[], mb=0.0, rows=both)
            out[row['name']] = kept
    return out
