"""GLT003/GLT004 — trace-time staging and jit closure hazards.

GLT003 bug class: a padded-array cache on Graph (PR 4; gone since
PR 29) rebound live instance state inside a function being traced by ``jax.jit`` — the attribute
ended up holding a leaked tracer, poisoning every later untraced read.
Any ``self.X = ...`` (or ``self.X[...] = ...``) executed at trace time
is that bug unless wrapped in ``jax.ensure_compile_time_eval()``.

GLT004 bug class: a jitted callee that *closes over* instance or
module-level arrays instead of taking them as arguments bakes the
array values into the compiled program — every swap of the underlying
object recompiles, violating the zero-steady-state-recompile contract
every engine test asserts (StreamSampler passes graph arrays as jit
ARGUMENTS for exactly this reason, PR 3).

Jit discovery is per-module and syntactic: decorated defs
(``@jax.jit``, ``@partial(jax.jit, ...)``), and direct wrap sites
(``jit(f)`` / ``jax.jit(self._m)``). Helpers merely *called from* a
jitted function are not chased — keep jit entry points honest and the
callees inherit the discipline.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Set

from ..core import FileCtx, Finding, ProjectCtx, Rule
from ._scopes import scope_of

_ARRAY_CTORS = ('jnp.', 'np.', 'jax.numpy.', 'numpy.')
_ARRAY_FNS = {'device_put', 'array', 'asarray', 'zeros', 'ones',
              'arange', 'full', 'empty'}


def _is_jit_expr(node: ast.AST) -> bool:
  """jit / jax.jit / pjit / eqx.filter_jit — as a bare expression."""
  dotted = Rule.dotted(node)
  last = dotted.split('.')[-1] if dotted else ''
  return last in ('jit', 'pjit', 'filter_jit')


def _jit_decorated(fn: ast.AST) -> bool:
  for dec in getattr(fn, 'decorator_list', []):
    if _is_jit_expr(dec):
      return True
    if isinstance(dec, ast.Call):
      if _is_jit_expr(dec.func):         # @jax.jit(static_argnums=...)
        return True
      if Rule.dotted(dec.func).split('.')[-1] == 'partial' and \
          dec.args and _is_jit_expr(dec.args[0]):
        return True                      # @partial(jax.jit, ...)
  return False


class _JitIndex:
  """Names of functions wrapped by jit somewhere in the module, plus
  module-level names bound to array-constructor calls."""

  def __init__(self, tree: ast.Module):
    self.wrapped_names: Set[str] = set()
    self.module_arrays: Set[str] = set()
    for node in ast.walk(tree):
      if isinstance(node, ast.Call) and _is_jit_expr(node.func):
        target = node.args[0] if node.args else None
        if isinstance(target, ast.Name):
          self.wrapped_names.add(target.id)
        elif isinstance(target, ast.Attribute):
          self.wrapped_names.add(target.attr)
    for stmt in tree.body:
      if isinstance(stmt, ast.Assign) and isinstance(stmt.value, ast.Call):
        fn = Rule.dotted(stmt.value.func)
        if fn.startswith(_ARRAY_CTORS) or \
            fn.split('.')[-1] in _ARRAY_FNS:
          for t in stmt.targets:
            if isinstance(t, ast.Name):
              self.module_arrays.add(t.id)


def _in_compile_time_eval(ancestors: List[ast.AST]) -> bool:
  for a in ancestors:
    if isinstance(a, ast.With):
      for item in a.items:
        expr = item.context_expr
        if isinstance(expr, ast.Call) and \
            Rule.dotted(expr.func).endswith('ensure_compile_time_eval'):
          return True
  return False


class TraceStagingRule(Rule):
  """Both GLT003 and GLT004 ride one jit-discovery pass; the rule is
  registered once and emits findings under either code."""

  code = 'GLT003'
  codes = ('GLT003', 'GLT004')
  name = 'trace-time-staging'
  applies_to = ()

  CODE_CLOSURE = 'GLT004'

  def check(self, ctx: FileCtx, project: ProjectCtx) -> Iterator[Finding]:
    index = _JitIndex(ctx.tree)
    for node in ast.walk(ctx.tree):
      if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        continue
      if not (_jit_decorated(node) or node.name in index.wrapped_names):
        continue
      yield from self._check_jitted(ctx, index, node)

  def _check_jitted(self, ctx: FileCtx, index: _JitIndex,
                    fn: ast.AST) -> Iterator[Finding]:
    params = {a.arg for a in fn.args.args + fn.args.kwonlyargs
              + fn.args.posonlyargs}
    if fn.args.vararg:
      params.add(fn.args.vararg.arg)
    if fn.args.kwarg:
      params.add(fn.args.kwarg.arg)
    self_free = 'self' not in params
    scope = scope_of(ctx.tree, fn) or fn.name

    stack: List[ast.AST] = []

    def visit(node: ast.AST) -> Iterator[Finding]:
      stack.append(node)
      # -- GLT003: instance mutation at trace time
      store_attr = None
      if isinstance(node, ast.Attribute) and \
          isinstance(node.ctx, (ast.Store, ast.Del)) and \
          isinstance(node.value, ast.Name) and node.value.id == 'self':
        store_attr = node.attr
      elif isinstance(node, ast.Subscript) and \
          isinstance(node.ctx, (ast.Store, ast.Del)) and \
          isinstance(node.value, ast.Attribute) and \
          isinstance(node.value.value, ast.Name) and \
          node.value.value.id == 'self':
        store_attr = node.value.attr
      if store_attr is not None and not _in_compile_time_eval(stack):
        yield Finding(
            rule='GLT003', path=ctx.relpath, line=node.lineno,
            col=node.col_offset, scope=scope, token=store_attr,
            message=(f'self.{store_attr} is rebound inside a jitted '
                     'callee: at trace time this stores a tracer into '
                     'live state (the padded-array cache leak, PR 4); '
                     'stage under jax.ensure_compile_time_eval() or '
                     'move the mutation out of the traced function'))
      # -- GLT004: closure over instance / module arrays
      if self_free and isinstance(node, ast.Attribute) and \
          isinstance(node.ctx, ast.Load) and \
          isinstance(node.value, ast.Name) and node.value.id == 'self':
        parent = stack[-2] if len(stack) >= 2 else None
        is_callee = isinstance(parent, ast.Call) and parent.func is node
        if not is_callee:
          yield Finding(
              rule=self.CODE_CLOSURE, path=ctx.relpath,
              line=node.lineno, col=node.col_offset, scope=scope,
              token=node.attr,
              message=(f'jitted function closes over self.{node.attr}: '
                       'closed-over arrays are baked into the compiled '
                       'program and every rebind recompiles — pass it '
                       'as an argument (StreamSampler contract)'))
      if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load) \
          and node.id in index.module_arrays and node.id not in params:
        yield Finding(
            rule=self.CODE_CLOSURE, path=ctx.relpath,
            line=node.lineno, col=node.col_offset, scope=scope,
            token=node.id,
            message=(f'jitted function closes over module-level array '
                     f'{node.id!r}: pass it as an argument so rebinding '
                     'the module global cannot silently recompile'))
      for child in ast.iter_child_nodes(node):
        yield from visit(child)
      stack.pop()

    for stmt in fn.body:
      yield from visit(stmt)
