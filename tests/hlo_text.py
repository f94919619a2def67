"""Questions the ZeRO tests ask of a compiled step's HLO text (CPU or TPU
compiler alike): which computations run inside a ``while`` loop — a layer
scan's body and whatever it calls — and which collectives sit there; and
of any step under remat: which forward attention kernels it runs again.
And one question of a jaxpr: the grids its Pallas calls run on. And how a
test comes by a jaxpr's text without a second trace: ``run_with_jaxpr`` runs a
function as one compiled program and gives the text that program was traced
to."""

import re

import jax

_HEAD = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_CALLEE = re.compile(
    r"(?:body|condition|to_apply|calls|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")


def computations(text):
    """{computation name: its lines} of an HLO module's text."""
    out, name = {}, None
    for line in text.splitlines():
        head = _HEAD.match(line)
        if head and not line.startswith(" "):
            name = head.group(1)
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return out


def _callees(lines):
    for line in lines:
        for one, many in _CALLEE.findall(line):
            if one:
                yield one
            for part in many.split(","):
                if part.strip():
                    yield part.strip().lstrip("%")


def loop_bodies(text):
    """{while-body name: lines of the body and of every computation it
    reaches}, one entry per ``while`` instruction of the module."""
    comps = computations(text)
    bodies = {}
    for lines in comps.values():
        for line in lines:
            if re.search(r"\bwhile\(", line):
                body = re.search(r"body=%?([\w.\-]+)", line).group(1)
                seen, todo = set(), [body]
                while todo:
                    name = todo.pop()
                    if name in seen or name not in comps:
                        continue
                    seen.add(name)
                    todo.extend(_callees(comps[name]))
                bodies[body] = [ln for name in sorted(seen)
                                for ln in comps[name]]
    return bodies


def instructions(lines, opcode):
    """The lines of ``lines`` that are ``opcode`` (or its async
    ``-start``) instructions."""
    pat = re.compile(rf"= \S+ {re.escape(opcode)}(?:-start)?\(")
    return [ln for ln in lines if pat.search(ln)]


def result_shape(line):
    """An instruction line's (first) result shape, as a tuple."""
    dims = re.search(r"= \(?\w+\[([\d,]*)\]", line).group(1)
    return tuple(int(d) for d in filter(None, dims.split(",")))


def result_elements(line):
    """Element count of an instruction line's (first) result shape."""
    n = 1
    for d in result_shape(line):
        n *= d
    return n


# the scopes round the forward attention kernels' ``pallas_call``s
# (ops/pallas/flash_attention.py: whole-row, chunked, window)
FORWARD_ATTENTION_SCOPES = ("flash_fwd", "flash_fwd_chunk", "swa_fwd")


def rematted_forward_attention(text):
    """The forward attention kernel calls a step runs a SECOND time, inside
    a rematted block's recomputation: ``rematted_scopes`` of
    ``FORWARD_ATTENTION_SCOPES``. Empty where the blocks keep ``flash_o`` /
    ``flash_lse``."""
    return rematted_scopes(text, FORWARD_ATTENTION_SCOPES)


# the scopes round the scan kernels' forward ``pallas_call``s
# (ops/pallas/gated_delta.py, ops/pallas/ssd.py)
FORWARD_SCAN_SCOPES = ("gdn_scan_fwd", "ssd_scan_fwd")


def rematted_scopes(text, scopes):
    """The distinct scope paths of ``text`` — a compiled step's
    (``op_name="..."``) or a lowered one's with ``debug_info``
    (``loc("...")``) — that hold JAX's ``rematted_computation`` and, below
    it, one of ``scopes``, cut at that scope: one path a call site, on the
    TPU one Pallas call (the scope holds nothing else), on the CPU the
    interpreter's many instructions under it."""
    sites = set()
    for path in re.findall(r'"([^"]*rematted_computation[^"]*)"', text):
        parts = path.split("/")
        if "rematted_computation" not in parts:
            continue
        start = parts.index("rematted_computation")
        for i in range(start, len(parts)):
            if parts[i] in scopes:
                # from the recomputation down: XLA leaves the path's head
                # off some of a call site's instructions
                sites.add("/".join(parts[start:i + 1]))
                break
    return sorted(sites)


def scan_forward_calls(grads, *args):
    """Of a jitted gradient function of a scan under remat: (the
    ``rematted_scopes`` of ``FORWARD_SCAN_SCOPES`` in its lowered text, the
    sorted output counts of its ``pallas_call``s — a primal call writes 1,
    a forward rule's its residuals too, the backward kernel the
    gradients)."""
    text = grads.lower(*args).as_text(debug_info=True)
    return (rematted_scopes(text, FORWARD_SCAN_SCOPES),
            sorted(len(eqn.outvars) for eqn in pallas_calls(
                jax.make_jaxpr(grads)(*args).jaxpr)))


def rematted_matmuls(text):
    """The matmuls a step runs a SECOND time, inside a rematted block's
    recomputation: the distinct scope paths, from ``rematted_computation``
    down, of every ``dot_general`` in a lowered text with ``debug_info``
    (``l0/attn/q_proj/dot_general``). A block that keeps what its backward
    pass reads of a projection has none of that projection's."""
    return sorted({path.split("rematted_computation/")[-1] for path in
                   re.findall(r'loc\("([^"]*rematted_computation/[^"]*'
                              r'dot_general[^"]*)"', text)})


def remat_report(loss, params, capsys):
    """Of ``loss(params)``'s gradient program under the model's own remat:
    (``rematted_forward_attention`` of its lowered text, the words of
    ``jax.ad_checkpoint.print_saved_residuals`` — what the backward pass is
    handed, a kept name as ``named 'flash_lse'`` — and the lowered
    program, for who wants its gradients)."""
    import jax
    lowered = jax.jit(jax.grad(loss)).lower(params)
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(loss, params)
    return (rematted_forward_attention(lowered.as_text(debug_info=True)),
            capsys.readouterr().out, lowered)


def kept_under_budget(loss, params, capsys, free_bytes):
    """``remat_report`` with ``free_bytes`` handed to the trace as an engine
    hands them (``parallel/mesh.layout_pins``), and the step's
    ``rematted_matmuls`` in place of its attention sites."""
    from deepspeed_tpu.parallel import mesh as mesh_lib
    with mesh_lib.layout_pins(None, remat_free_bytes=free_bytes):
        _, handed, lowered = remat_report(loss, params, capsys)
    return (rematted_matmuls(lowered.as_text(debug_info=True)), handed,
            lowered)


def run_with_jaxpr(fn, *args):
    """(``fn(*args)`` run as ONE compiled program, the text of the jaxpr
    that program was traced to): a whole model is traced once for both, and
    none of its primitives is dispatched and compiled on its own."""
    traced = jax.jit(fn).trace(*args)
    return traced.lower().compile()(*args), str(traced.jaxpr)


def pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr and of the jaxprs inside it
    (a remat, a custom VJP's rules), in program order."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from pallas_calls(sub)


def pallas_element_rows(jaxpr):
    """Rows of every [rows, D] operand block a ``pallas_call`` under a jaxpr
    reads at an ELEMENT offset (``pl.Element``: the window kernels' bands),
    in program order."""
    from jax.experimental import pallas as pl
    return [bm.block_shape[1].block_size
            for eqn in pallas_calls(jaxpr)
            for bm in eqn.params["grid_mapping"].block_mappings
            if len(bm.block_shape) == 3
            and isinstance(bm.block_shape[1], pl.Element)]


def pallas_grids(jaxpr):
    """The grid of every ``pallas_call`` of a jaxpr and of the jaxprs inside
    it, in program order."""
    return [tuple(eqn.params["grid_mapping"].grid)
            for eqn in pallas_calls(jaxpr)]
