"""What the files that compile for a described chip share (pytest does not
collect this one): the fixtures ``one_chip`` — the v5e's first device, from a
topology described inside the fixture, never at import — and
``no_compile_cache``, ``_compile``, and the readers of an optimized module's
text.

The TPU's compiler is installed here and compiles for a described v5e
(guides/on-chip-measurement §2).  Nothing runs, so such a test says nothing of
results or times — it catches what Mosaic or XLA:TPU would refuse (a block
over the VMEM limit, a misaligned slice) and reads what the compiled program
keeps, copies and calls, before chip time is spent.

One process may load libtpu unless ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` is set, as
the driver's tier-1 command sets it (six ``xdist`` workers, ``--dist
loadfile``, 1470 s): two workers describing the topology and compiling at once
were tried under it and hold, so the cases are two files —
tests/test_tpu_compile.py (the flash kernels' shapes, the held-expert layers)
and tests/test_tpu_compile_layers.py (mixers, the recurrences' layers, whole
steps).  Two and not more: ``loadfile`` hands out the files with the most cases
first, so a file of a dozen heavy cases starts last and is the run's tail, and
the TPU compiler takes every core — four such files at once doubled each
other's times and starved the clock-bound tests beside them (PR 66).  Without
the variable a second process's ``one_chip`` skips (the serial Tier-1 line of
ROADMAP.md runs one process and needs none).
"""

import math
import re

import jax
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).compile()


_ITEM = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
_SHAPE = r"\b(bf16|f32|s32|u32|pred)\[([\d,]*)\]"


def _bytes_of(shapes: str) -> int:
    return sum(math.prod(int(d) for d in dims.split(",") if d) * _ITEM[t]
               for t, dims in re.findall(_SHAPE, shapes))


def _top_level(text: str) -> list:
    """The ENTRY computation's instructions of an optimized module as (name,
    opcode, result shapes, operand names, is a Pallas kernel, ``op_name``)."""
    entry = text[text.index("\nENTRY "):]
    found = []
    for line in entry[:entry.index("\n}")].splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*)", line)
        if not m:
            continue
        body = m.group(2).split(", metadata=")[0]
        op = re.search(r" ([a-z\-]+)\(", body)
        args = body[op.end():].split(")", 1)[0]
        found.append((m.group(1), op.group(1), body[:op.start()],
                      [a.lstrip("%") for a in re.findall(r"%[\w.\-]+", args)],
                      "tpu_custom_call" in body,
                      (re.search(r'op_name="([^"]*)"', line) or ["", ""])[1]))
    return found


def _relayouts_under(text: str, scope: str, least_bytes: int) -> list:
    """The ``transpose`` and ``copy`` instructions of an optimized module (any
    computation, fused ones too) whose result holds at least ``least_bytes``
    and whose ``op_name`` lies under ``scope``.  XLA:TPU writes a change of
    layout as a ``copy`` between two layouts; a reshape that is none is a
    ``bitcast`` and is not listed."""
    import math
    import re

    item = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* (transpose|copy)\(", line)
        if not m or scope not in (re.search(r'op_name="([^"]*)"', line) or [""])[0]:
            continue
        size = math.prod(int(d) for d in m.group(3).split(",") if d) * item.get(m.group(2), 4)
        if size >= least_bytes:
            found.append(f"{m.group(1)}: {m.group(4)} of {m.group(2)}[{m.group(3)}]")
    return found


def _slices_moved(text: str, least_bytes: int) -> list:
    """The ``dynamic-slice`` and ``dynamic-update-slice`` instructions of an
    optimized module (any computation) that move at least ``least_bytes``: a
    slice's result, an update's written operand — what a ``lax.scan`` reads
    from and writes to its stack a turn."""
    defined = re.compile(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\((.*)$", re.M)

    def size(dtype, dims):
        return math.prod(int(d) for d in dims.split(",") if d) * _ITEM.get(dtype, 4)

    sizes = {name: size(dtype, dims) for name, dtype, dims, _, _ in defined.findall(text)}
    found = []
    for name, dtype, dims, op, operands in defined.findall(text):
        if op == "dynamic-slice":
            moved = size(dtype, dims)
        elif op == "dynamic-update-slice":
            moved = sizes[re.findall(r"%([\w.\-]+)", operands)[1]]
        else:
            continue
        if moved >= least_bytes:
            found.append(f"{name}: {op} of {moved / 2**20:.0f} MiB")
    return found


def _cuts_written_under(text: str, scope: str, least_bytes: int) -> list:
    """The top-level instructions under ``scope`` that WRITE columns cut out
    of an array, put back or rows shifted — a ``slice`` | ``pad`` |
    ``concatenate`` (dynamic ones too) of its own, or a fusion XLA named for
    one — in at least ``least_bytes``.  (A slice INSIDE a fusion is an index
    and writes nothing: the gated norm reads z's columns so.)"""
    return [f"{name}: {opcode} {result.strip()}" for name, opcode, result, _, kernel, path
            in _top_level(text)
            if scope in path and not kernel and _bytes_of(result) >= least_bytes
            and re.search(r"slice|pad|concatenate", name if opcode == "fusion" else opcode)]


def _kernel_operands(text: str, kernel: str) -> list:
    """The operands' types (``bf16[32,16384,128]``), in order, of the one
    Pallas call named ``kernel`` (a whole word of its ``op_name``)."""
    ops = _top_level(text)
    types = {name: re.sub(r"\{.*", "", result.strip().lstrip("(")) for name, _, result, *_ in ops}
    (call,) = [o for o in ops if o[4] and re.search(rf"\b{kernel}\b", o[5])]
    return [types[a] for a in call[3]]


def _named_bytes(text: str) -> int:
    """Bytes that the top-level operations OUTSIDE the Pallas kernels name:
    each ``fusion`` | ``copy`` | ``broadcast`` | ``reduce`` | ``convert``'s
    result and operands (a matrix product is a fusion here; the asynchronous
    copies that stage an operand for one are not counted twice)."""
    ops = _top_level(text)
    size = {name: _bytes_of(result) for name, _, result, *_ in ops}
    return sum(size[name] + sum(size.get(a, 0) for a in args)
               for name, opcode, _, args, kernel, _ in ops
               if not kernel and opcode in ("fusion", "copy", "broadcast", "reduce", "convert"))


def _sources(ops: list, name: str) -> list:
    """``name``'s operands, through ``get-tuple-element``s and ``bitcast``s."""
    by_name = {o[0]: o for o in ops}
    found, todo = [], [name]
    while todo:
        for arg in by_name[todo.pop()][3]:
            found.append(arg)
            if arg in by_name and by_name[arg][1] in ("get-tuple-element", "bitcast"):
                todo.append(arg)
    return found
