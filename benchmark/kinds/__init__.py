"""How a group of a mix is generated, parsed, counted and held to the
reference: one module a kind, named by the group's ``kind``. A group
that names none is what every group was before there was a choice: a
rectangle of ``h``/``c``/``g`` series, ``local``, or ``forwarded`` where
it states how many forwarders report a series (``fan_in``) or is their
messages' ``marker``.

A kind's module gives, for the groups of a mix that are its own
(``mine``: their indices in the mix's ``groups``):

    table(group, percentiles, flushes)
        what an emission holds for the group, empty
    land(em, cols, group, idx, suf, tags, val)
        the parsed rows ``<prefix><idx>[.<suf>]`` of one body land in
        ``cols`` (``tags()`` gives the text of their ``tags`` arrays);
        rows it cannot place count in ``em.stray``, rows seen twice in
        ``em.dup``
    lines_in(cols, group, sent)
        the lines ``cols`` accounts for; ``sent`` is the group's
        ``values`` in the round the emission flushed, or None where the
        caller has no such round
    compare(t, mine, emissions, rounds, window, span, tail, groups,
            percentiles, limits, sent)
        adds to the tallies ``t`` (``lib/reference.py`` ``compare``)
    synthesize(out, mine, rounds, window, groups, percentiles, precision,
               moved, control, limits)
        writes the reference's own emissions into ``out``
    live_series(group)
        the series of the group that an interval keeps live

and, for a mix that ``generators/groups_by_kind.py`` builds:

    generate(group, rng, seed, index) -> (lines, sent)
        the group's lines of round ``index`` as bytes, and what the
        reference needs of them
    settle(group, sent, position) -> (values, last)
        once the round's order is drawn: ``position`` of each of those
        lines in it
    lines_a_round(group), warm_line(group)
"""

from __future__ import annotations

import importlib

DEFAULT, DEFAULT_FORWARDED = "local", "forwarded"


def name_of(group: dict) -> str:
    if "kind" in group:
        return group["kind"]
    forwarded = "fan_in" in group or bool(group.get("marker"))
    return DEFAULT_FORWARDED if forwarded else DEFAULT


def _module(name: str):
    return importlib.import_module("benchmark.kinds." + name)


def of(group: dict):
    """The module of the group's kind, found by name."""
    return _module(name_of(group))


def by_kind(groups: list) -> list:
    """``[(module, [indices of its groups])]``, kinds in the order in
    which the mix first names them."""
    found: dict = {}
    for g, group in enumerate(groups):
        found.setdefault(name_of(group), []).append(g)
    return [(_module(name), mine) for name, mine in found.items()]
