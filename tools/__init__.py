"""Operator tooling (reports over the program's own dumps, the chaos
campaign, ceph-lint).

A package so the tests can import the reusable entry points
(``tools.chaos_run``, ``tools.ceph_lint``) without path hacks; each
script remains directly runnable too.
"""
