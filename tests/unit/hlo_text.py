"""The "off means the same program" contract, as one helper: a compiled
program's text without what only records WHERE it was traced from."""

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
