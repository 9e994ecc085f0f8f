"""Generate man pages for every CLI (≙ the reference's man/ tree:
jubactl.8, jubaconfig.8, jubaconv.1, jubavisor.8, jubadump.1,
jubatus_server.8, jubatus_proxy.8, regenerated by man/regenerate.sh).

Pages are produced from each tool's live --help output, so they can never
drift from the argparse definitions:

    python docs/gen_man.py [outdir]     # default docs/man/
"""

from __future__ import annotations

import os
import re
import subprocess
import sys
import time

#: (module, page name, man section, one-line description)
PAGES = [
    ("jubatus_tpu.cmd.jubactl", "jubactl", 8,
     "cluster control: start/stop via supervisors, save/load/status"),
    ("jubatus_tpu.cmd.jubaconfig", "jubaconfig", 8,
     "validate and manage engine configs in the coordination store"),
    ("jubatus_tpu.cmd.jubaconv", "jubaconv", 1,
     "offline json to datum to feature-vector conversion debugger"),
    ("jubatus_tpu.cmd.jubavisor", "jubavisor", 8,
     "per-host process supervisor daemon, RPC-controlled"),
    ("jubatus_tpu.cmd.jubadump", "jubadump", 1,
     "convert saved model files to JSON"),
    ("jubatus_tpu.server", "jubatus_server", 8,
     "engine server main (jubaclassifier, jubarecommender, ...)"),
    ("jubatus_tpu.server.proxy", "jubatus_proxy", 8,
     "query-routing proxy (juba*_proxy)"),
    ("jubatus_tpu.coord.server", "jubacoordd", 8,
     "coordination service daemon (lock/membership/config store)"),
]


def _esc(line: str) -> str:
    line = line.replace("\\", "\\\\")
    if line.startswith(("'", ".")):
        line = "\\&" + line
    return line


def render_page(name: str, section: int, desc: str, help_text: str) -> str:
    date = time.strftime("%Y-%m-%d")
    out = [
        f'.TH "{name.upper()}" "{section}" "{date}" "jubatus_tpu" "Jubatus TPU"',
        ".SH NAME",
        f"{name} \\- {desc}",
        ".SH SYNOPSIS",
        ".nf",
    ]
    lines = help_text.splitlines()
    # argparse help: usage block first, then description/options
    in_usage = True
    body: list = []
    for ln in lines:
        if in_usage:
            if ln.strip() and not ln.startswith(("usage:", " ")):
                in_usage = False
            else:
                out.append(_esc(ln.replace("usage: ", "")))
                continue
        body.append(ln)
    out.append(".fi")
    in_options = False
    wrote_description = False
    for ln in body:
        s = ln.strip()
        if re.match(r"^(positional arguments|options|optional arguments):", s):
            if not in_options:  # one OPTIONS section, argparse groups merge
                out.append(".SH OPTIONS")
                in_options = True
            continue
        if not s:
            if wrote_description or in_options:
                out.append(".PP" if not in_options else ".sp")
            continue
        if in_options and ln.startswith("  ") and not ln.startswith("      "):
            out.append(".TP")
            out.append(f"\\fB{_esc(s)}\\fP")
            continue
        if not in_options and not wrote_description:
            out.append(".SH DESCRIPTION")  # only when there is body text
            wrote_description = True
        out.append(_esc(s))
    out += [
        ".SH SEE ALSO",
        ", ".join(f"\\fB{n}\\fP({s})" for _, n, s, _ in PAGES if n != name),
        "",
    ]
    return "\n".join(out)


def main(argv=None) -> int:
    outdir = (argv or sys.argv[1:] or ["docs/man"])[0]
    os.makedirs(outdir, exist_ok=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for module, name, section, desc in PAGES:
        r = subprocess.run(
            [sys.executable, "-m", module, "--help"],
            capture_output=True, text=True, env=env,
        )
        help_text = r.stdout or r.stderr
        if r.returncode != 0 or "usage" not in help_text:
            print(f"SKIP {name}: --help failed", file=sys.stderr)
            continue
        path = os.path.join(outdir, f"{name}.{section}")
        with open(path, "w", encoding="utf-8") as f:
            f.write(render_page(name, section, desc, help_text))
        print(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
