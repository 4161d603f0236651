"""Reading an HLO module's text: which ops the loop bodies of a program run."""
import re

_HEADER = re.compile(r"^(?:ENTRY )?%?([\w.\-]+)[ (].*\{\s*$", re.M)  # unindented
_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%?([\w.\-]+)"
)
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_WHILE_BODY = re.compile(r"\bwhile\(.*?\bbody=%?([\w.\-]+)")


def _computations(hlo: str) -> dict[str, str]:
    heads = list(_HEADER.finditer(hlo))
    ends = [h.start() for h in heads[1:]] + [len(hlo)]
    return {h.group(1): hlo[h.start():end] for h, end in zip(heads, ends)}


def while_body_text(hlo: str) -> str:
    """The text of every computation a ``while`` body of ``hlo`` runs,
    through fusions, calls and nested control flow."""
    comps = _computations(hlo)
    todo = list(_WHILE_BODY.findall(hlo))
    assert todo, "no while loop in the module"
    seen: set[str] = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        text = comps[name]
        todo += _CALLED.findall(text)
        for group in _BRANCHES.findall(text):
            todo += [b.strip().lstrip("%") for b in group.split(",")]
    return "\n".join(comps[n] for n in sorted(seen))


def has_scatter(text: str) -> bool:
    return re.search(r"\bscatter\(", text) is not None
