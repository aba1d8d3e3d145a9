"""Reading a compiled program's text: the "off means the same program"
contract (the text without what only records WHERE it was traced from), and
the collectives a partitioned program holds."""

import re

_TABLES = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:\d+ .*\n)*",
    re.MULTILINE)
_METADATA = re.compile(r",?\s*metadata=\{[^{}]*\}")


def program_text(compiled) -> str:
    """``compiled.as_text()`` less the source-location tables and each
    instruction's ``metadata={...}``: two lowerings of one function from
    two lines of a test differ in those and in nothing the chip runs."""
    return _METADATA.sub("", _TABLES.sub("", compiled.as_text()))


_COLLECTIVE = re.compile(
    r"= (\([^=]*\)|\S+) (all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(?:-start)?\(")
_RESULT = re.compile(r"\b([a-z]+\d+)\[([\d,]*)\]")


def collectives(text: str):
    """``(kind, [(dtype, dims), ...], in the entry computation?)`` of every
    collective instruction of a compiled program's text, from its result
    type (a tuple's members each).  An instruction outside the entry
    computation sits in a loop body or another called computation."""
    out, entry = [], False
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            entry = line.startswith("ENTRY ")
        m = _COLLECTIVE.search(line)
        if m:
            out.append((m.group(2), [
                (dtype, tuple(int(d) for d in dims.split(",") if d))
                for dtype, dims in _RESULT.findall(m.group(1))], entry))
    return out
