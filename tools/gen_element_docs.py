"""Regenerate docs/elements.md from the live element registry.

The reference's analog surface is ``gst-inspect-1.0``; ours is
``python -m nnstreamer_tpu inspect <name>``. This script renders the same
registry data as markdown so the docs can't drift from the code:

    python tools/gen_element_docs.py          # rewrites docs/elements.md
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


EPILOGUE = """## Universal properties

Every element additionally accepts `config-file` — a path of
`key=value` lines applied as properties at set time (the reference's
`gst_tensor_parse_config_file`). It does not appear in the per-element
lists above because it is implemented once in the element base outside
the property registry. (`silent`, the other universal property, IS
listed per element.)

## Golden corpus

`tests/golden/*.bin` pins the exact output bytes of all 12 decoder modes
(the reference's SSAT `callCompareTest` pattern). Regenerate deliberately
with `python tests/golden/generate.py` when an output change is intended.
"""


def main() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")  # registry walk: no chip needed

    from nnstreamer_tpu.registry.elements import element_factories, get_factory

    lines = [
        "# Element reference",
        "",
        "Auto-generated from the element registry "
        "(`python tools/gen_element_docs.py`; "
        "`python -m nnstreamer_tpu inspect <name>` shows the same live).",
        "",
        "Pipelines built from these elements can be validated *before* "
        "execution with the static linter — `python -m nnstreamer_tpu "
        "lint \"<launch string>\"` cross-checks element names, "
        "properties, caps compatibility, and perf hazards against this "
        "registry; see [lint.md](lint.md) for the rule catalog.",
    ]
    for name in element_factories():
        cls = get_factory(name)
        lines += ["", f"## `{name}`", ""]
        doc = (cls.__doc__ or "").strip()
        if doc:
            # first PARAGRAPH (up to a blank line), not just the first
            # line — docstrings legitimately wrap mid-sentence
            para = doc.split("\n\n")[0]
            lines += [" ".join(ln.strip() for ln in para.splitlines()), ""]
        sinks = ", ".join(f"`{t.name_template}`"
                          for t in cls.SINK_TEMPLATES) or "—"
        srcs = ", ".join(f"`{t.name_template}`"
                         for t in cls.SRC_TEMPLATES) or "—"
        lines.append(f"- sink pads: {sinks}; src pads: {srcs}")
        from nnstreamer_tpu.registry.elements import merged_properties

        props = merged_properties(cls)
        if props:
            lines.append("- properties:")
            for key, prop in props.items():
                dash = key.replace("_", "-")
                doc_str = f" — {prop.doc}" if prop.doc else ""
                lines.append(f"  - `{dash}` (default `{prop.default!r}`){doc_str}")
    lines += ["", EPILOGUE.rstrip()]
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "..", "docs", "elements.md")
    with open(out, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    print(f"wrote {os.path.normpath(out)} ({len(lines)} lines, "
          f"{len(element_factories())} elements)")


if __name__ == "__main__":
    main()
