"""Source-level hygiene guards for kernel/ref pairs.

Float division by a *constant* is banned in kernel-adjacent code: under jit
XLA canonicalizes ``x / c`` to ``x * (1/c)``, which differs by up to 1 ULP
from a true divide, so a kernel and its reference can disagree on
round-half cases and break the bit-exact tests (the quantize kernel hit
exactly this; it now multiplies by an explicit reciprocal).  Audit result
as of the entropy-subsystem PR: motion, polymul, seal, entropy and the
kernel-callable ChaCha core are integer-only; quantize carries the
reciprocal-multiply fix.  This test keeps it that way.
"""

import ast
import io
import os
import token
import tokenize

import pytest

import repro.kernels as _k
from repro.core.crypto import chacha as _chacha

KERNEL_ROOT = os.path.dirname(_k.__file__)


def _kernel_sources():
    files = [_chacha.__file__]  # kernel-callable ChaCha core
    for dirpath, _, names in os.walk(KERNEL_ROOT):
        files += [
            os.path.join(dirpath, n) for n in names if n.endswith(".py")
        ]
    return sorted(files)


def _float_const_divisions(source: str):
    """Yield (line, text) for each ``<array-ish> / <float literal>``.

    Token-based so docstrings/comments can't false-positive.  A literal
    numerator (``1.0 / 127.0``) is allowed: Python folds it to one exact
    constant before tracing, no XLA rewrite involved.  ``x / traced`` is
    allowed: both sides of a kernel/ref pair trace the same divide op.
    """
    toks = [
        t
        for t in tokenize.generate_tokens(io.StringIO(source).readline)
        if t.type not in (token.NL, token.NEWLINE, token.INDENT, token.DEDENT,
                          token.COMMENT)
    ]
    for i, t in enumerate(toks):
        if t.type != token.OP or t.string != "/" or not (0 < i < len(toks) - 1):
            continue
        prev, nxt = toks[i - 1], toks[i + 1]
        # any numeric literal divisor: jnp's `/` is true division even for
        # int literals, so `x / 127` hits the same reciprocal rewrite as
        # `x / 127.0` (`//` tokenizes as its own operator and is exempt)
        numerator_arrayish = (
            prev.type == token.NAME
            or (prev.type == token.OP and prev.string in (")", "]"))
        )
        if nxt.type == token.NUMBER and numerator_arrayish:
            yield t.start[0], t.line.strip()


@pytest.mark.parametrize("path", _kernel_sources(), ids=os.path.basename)
def test_no_float_division_by_constant(path):
    with open(path) as f:
        offenders = [
            f"{path}:{line}: {text}"
            for line, text in _float_const_divisions(f.read())
        ]
    assert not offenders, (
        "float division by a constant in kernel code (jit rewrites x/c to "
        "x*(1/c); use an explicit exact reciprocal multiply or integer "
        "ops):\n" + "\n".join(offenders)
    )


def _banned_tpu_constructs(source: str):
    """Yield (line, text) for ``searchsorted`` uses and ``.at[...].add``
    scatter-adds.

    Both serialize on TPU (and are slow scalar loops on the CPU backend
    too): the entropy coder replaced its 4096-entry ``searchsorted``
    decode-table build with a cumulative-bucket fill (scatter-max +
    running max) and its scatter-add histogram with a one-hot matmul, and
    this test keeps those TPU-hostile constructs from silently returning
    to any kernel source.  Token-based so docstrings/comments cannot
    false-positive; ``.at[...].set`` / ``.at[...].max`` stay allowed (the
    emission pack and the bucket fill use them on small index sets).
    """
    toks = [
        t
        for t in tokenize.generate_tokens(io.StringIO(source).readline)
        if t.type not in (token.NL, token.NEWLINE, token.INDENT, token.DEDENT,
                          token.COMMENT)
    ]
    for i, t in enumerate(toks):
        if t.type == token.NAME and t.string == "searchsorted":
            yield t.start[0], t.line.strip()
        # the scatter-add pattern: OP'.' NAME'at' OP'[' ... OP']' OP'.'
        # NAME'add' OP'('
        if (
            t.type == token.OP and t.string == "."
            and i + 2 < len(toks)
            and toks[i + 1].type == token.NAME and toks[i + 1].string == "at"
            and toks[i + 2].type == token.OP and toks[i + 2].string == "["
        ):
            depth = 0
            for k in range(i + 2, len(toks)):
                if toks[k].type == token.OP and toks[k].string == "[":
                    depth += 1
                elif toks[k].type == token.OP and toks[k].string == "]":
                    depth -= 1
                    if depth == 0:
                        if (
                            k + 3 < len(toks)
                            and toks[k + 1].string == "."
                            and toks[k + 2].string == "add"
                            and toks[k + 3].string == "("
                        ):
                            yield t.start[0], t.line.strip()
                        break


def _entropy_sources():
    """The entropy column: the coder package plus the fused write chain."""
    files = []
    for sub in ("entropy", "fused"):
        root = os.path.join(KERNEL_ROOT, sub)
        for dirpath, _, names in os.walk(root):
            files += [
                os.path.join(dirpath, n) for n in names if n.endswith(".py")
            ]
    return sorted(files)


def _uses_name(node: ast.AST, name: str) -> bool:
    return any(
        isinstance(n, ast.Name) and n.id == name for n in ast.walk(node)
    )


def _induction_indexed_fori_loops(source: str):
    """Yield (line, text) for each ``fori_loop`` whose body indexes by the
    induction variable — a per-row subscript gather/update inside the
    carry chain, the serializing construct the two-phase encode removed
    (XLA:CPU cannot vectorize across trips whose memory access depends on
    the trip index; each row waits on the last).  A ``fori_loop`` whose
    body never subscripts by its induction variable (reduction-style
    carries) stays allowed.
    """
    tree = ast.parse(source)
    defs = {
        n.name: n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)
    }
    # inside a Pallas kernel body (``_*_kernel``) a fori_loop over VMEM
    # ref rows is the native sequential form; the rule is for XLA code
    in_kernel = {
        id(n)
        for k in ast.walk(tree)
        if isinstance(k, ast.FunctionDef) and k.name.endswith("_kernel")
        for n in ast.walk(k)
    }
    src_lines = source.splitlines()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or id(node) in in_kernel:
            continue
        fn = node.func
        fn_name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None
        )
        if fn_name != "fori_loop" or len(node.args) < 3:
            continue
        body = node.args[2]
        if isinstance(body, ast.Name):
            body = defs.get(body.id)
        if body is None or not getattr(body, "args", None):
            continue
        params = body.args.args
        if not params:
            continue
        ivar = params[0].arg
        inner = body.body if isinstance(body, ast.FunctionDef) else [body.body]
        for stmt in inner:
            for n in ast.walk(stmt):
                hit = (
                    isinstance(n, ast.Subscript) and _uses_name(n.slice, ivar)
                ) or (
                    isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr.startswith("dynamic_")
                    and any(_uses_name(a, ivar) for a in n.args)
                )
                if hit:
                    yield node.lineno, src_lines[node.lineno - 1].strip()
                    return


@pytest.mark.parametrize("path", _entropy_sources(), ids=os.path.basename)
def test_no_induction_indexed_fori_loop_in_entropy(path):
    """No per-row ``fori_loop`` carry chain in the XLA-level entropy code
    (ops, oracle): there it serializes a dynamic-update chain on every
    backend.  Pallas kernel bodies are exempt — their row loops walk VMEM
    refs, the sequential form Mosaic lowers natively."""
    with open(path) as f:
        offenders = [
            f"{path}:{line}: {text}"
            for line, text in _induction_indexed_fori_loops(f.read())
        ]
    assert not offenders, (
        "induction-indexed fori_loop in entropy coder code (serializes "
        "rows on every backend — use the two-phase batched schedule: "
        "precompute the emission schedule with tensor ops, then one "
        "gather/select pass):\n" + "\n".join(offenders)
    )


@pytest.mark.parametrize("path", _kernel_sources(), ids=os.path.basename)
def test_no_searchsorted_or_scatter_add(path):
    with open(path) as f:
        offenders = [
            f"{path}:{line}: {text}"
            for line, text in _banned_tpu_constructs(f.read())
        ]
    assert not offenders, (
        "TPU-hostile construct in kernel code (searchsorted lowers to a "
        "serial binary-search gather loop, .at[...].add to a serializing "
        "scatter; use a cumulative-bucket fill / one-hot matmul instead):\n"
        + "\n".join(offenders)
    )
