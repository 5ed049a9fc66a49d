"""A compiled module's text (`compiled.as_text()`) by computation: what the
tests that count ops inside a `conditional`'s branches share."""

import re


def computations(text):
    """{computation: body}."""
    return dict(re.findall(r"^(?:ENTRY )?%([\w.\-]+) \(.*?\{\n(.*?)^\}", text,
                           re.M | re.S))


def body_with_callees(comps, name):
    """The text of computation `name` and of everything it calls."""
    seen, todo = {}, [name]
    while todo:
        name = todo.pop()
        if name not in seen:
            seen[name] = comps[name]
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)",
                               comps[name])
    return "\n".join(seen.values())


def conditionals(text):
    """[(result types, [each branch's text with its callees])] of every
    `conditional` in the module."""
    comps = computations(text)
    return [(results, [body_with_callees(comps, b.strip(" %"))
                       for b in branches.split(",")])
            for results, branches in re.findall(
                r"= \((.*?)\) conditional\(.*?branch_computations=\{(.*?)\}", text)]
